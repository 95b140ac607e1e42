"""Decoupled exchange operators as JAX collectives (paper §3.2).

The paper replaces the classic Volcano exchange operator with *decoupled*
exchange operators that only talk to a per-server communication multiplexer,
which in turn performs an all-to-all shuffle over ``n - 1`` conflict-free
round-robin phases (§3.2.3).  This module is the JAX/TPU rendition:

* a *parallel unit* is a device along one mesh axis (inside ``shard_map``),
* a *message* is the per-destination chunk of a device-local array,
* a *phase* is a ``jax.lax.ppermute`` whose permutation is one phase of a
  :class:`repro.core.schedule.Schedule` — a cyclic shift routes along
  disjoint torus links, so no link is shared within a phase, which is
  exactly the property the paper's switch scheduling establishes,
* the *message pool / zero-copy* discipline becomes buffer donation and the
  ping-pong accumulation of :func:`scheduled_all_to_all_consume` (process
  each message as it arrives instead of materializing all of them — the
  paper's workers do the same with incoming tuples).

The partition hot path (paper §3.2.1's per-tuple CRC32 + message-buffer
fill) has two implementations, selected by ``pack_impl``:

* ``"xla"`` — reference: a ``[rows, num_dest + 1]`` one-hot + cumsum.
  O(rows x destinations) memory and FLOPs; fine for small meshes, dominates
  the shuffle itself as the mesh grows.
* ``"pallas"`` — the fused kernel of :mod:`repro.kernels.hash_partition`:
  hash + validity mask + block-local rank + block histogram in one pass,
  combined by an ``[nblocks, bins]`` exclusive scan and a flat gather.  The
  row-global one-hot never materializes; cost scales with
  ``rows + nblocks x destinations``.

:func:`hash_shuffle` additionally supports a *chunked double-buffered
pipeline* (``num_chunks > 1``): rows are split into chunks, and chunk
``k + 1`` is packed before chunk ``k``'s ppermute phases are issued.  The
pack has no data dependence on the in-flight shuffle, so XLA's async
scheduler can overlap partition compute with DMA — the TPU rendition of the
paper's multiplexer sending message ``k`` while the workers fill ``k + 1``.
``transport_chunks`` further splits each phase's message into independent
ppermutes (finer DMA granularity at one extra launch each).

The chunking contract (enforced by assertions here; the multiplexer layer
pre-checks and falls back with a warning instead): ``num_chunks`` divides
both the row count and ``capacity``, and ``transport_chunks`` divides the
per-chunk capacity ``capacity / num_chunks``.  Every (impl, pack_impl,
chunking) combination delivers the same rows to the same devices; only the
padding layout differs (chunked shuffles pad at chunk boundaries).

Overflow semantics: packing is capacity-bounded (fixed-size message
buffers, the paper's registered message pool), so rows beyond a
destination's capacity are *counted, not shipped* — :func:`hash_shuffle`
returns the psum'd ``dropped`` total and callers decide the policy.  The
relational layer (:mod:`repro.relational.distributed`) sizes capacity to
the static zero-drop bound and raises on any nonzero count: overflow is an
error, never silent row loss.

Everything here must be called inside ``shard_map`` (a named mesh axis in
scope).  The pjit/auto-sharded layers above call these through
:mod:`repro.core.multiplexer`, which owns the knob *values* — hand-set or
derived from the topology cost model by :mod:`repro.core.autotune`.
"""

from __future__ import annotations

from typing import Any, Callable, Literal

import jax
import jax.numpy as jnp
from jax import lax

from .schedule import Schedule, make_schedule

AllToAllImpl = Literal["xla", "round_robin", "one_factorization"]
PackImpl = Literal["xla", "pallas"]


# ----------------------------------------------------------------------------
# All-to-all.
# ----------------------------------------------------------------------------

def xla_all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
    """Baseline: XLA's monolithic all-to-all (the 'unscheduled' transport).

    ``x[j]`` (leading dim = axis size) is the chunk destined for device ``j``;
    the result's ``y[j]`` is the chunk received from device ``j``.
    """
    n = lax.axis_size(axis_name)
    assert x.shape[0] == n, f"leading dim {x.shape[0]} != axis size {n}"
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=True)


def _phase_tables(schedule: Schedule):
    """Static per-phase (targets_by_src, sources_by_dst) lookup arrays."""
    tgt, src = [], []
    for phase in schedule.phases:
        t = [0] * schedule.n
        s = [0] * schedule.n
        for a, b in phase:
            t[a] = b
            s[b] = a
        tgt.append(t)
        src.append(s)
    return jnp.asarray(tgt, jnp.int32), jnp.asarray(src, jnp.int32)


def scheduled_all_to_all(
    x: jax.Array,
    axis_name: str,
    schedule: str = "shift",
    num_chunks: int = 1,
) -> jax.Array:
    """The paper's phased round-robin all-to-all (Fig 10a) via ppermute.

    Same contract as :func:`xla_all_to_all` but decomposed into ``n - 1``
    conflict-free permutation phases.  Each phase of the default ``shift``
    schedule is a cyclic shift ``i -> i + k``, which a torus routes over
    link-disjoint paths; the XLA async scheduler may overlap consecutive
    phases' DMAs with unrelated compute.

    ``num_chunks > 1`` splits each per-destination message along its second
    axis into sub-messages shipped by independent ppermutes — smaller
    in-flight transfers that the async scheduler can pipeline (double
    buffering at the transport level).  Requires ``x.ndim >= 2`` and
    ``x.shape[1] % num_chunks == 0``.
    """
    n = lax.axis_size(axis_name)
    assert x.shape[0] == n, f"leading dim {x.shape[0]} != axis size {n}"
    if n == 1:
        return x
    if num_chunks > 1:
        assert x.ndim >= 2 and x.shape[1] % num_chunks == 0, (
            f"num_chunks={num_chunks} must divide message dim "
            f"{x.shape[1] if x.ndim >= 2 else None}"
        )
    sched = make_schedule(n, schedule)
    me = lax.axis_index(axis_name)
    tgt_tab, src_tab = _phase_tables(sched)

    # Own chunk stays put: y[me] = x[me].
    own = lax.dynamic_slice_in_dim(x, me, 1, axis=0)
    y = lax.dynamic_update_slice_in_dim(jnp.zeros_like(x), own, me, axis=0)

    sub = x.shape[1] // num_chunks if num_chunks > 1 else 0
    for k in range(sched.num_phases):
        send_to = tgt_tab[k, me]  # who I send to this phase
        recv_from = src_tab[k, me]  # who I receive from this phase
        chunk = lax.dynamic_slice_in_dim(x, send_to, 1, axis=0)
        if num_chunks == 1:
            got = lax.ppermute(chunk, axis_name, sched.phase_permutation(k))
        else:
            parts = [
                lax.ppermute(
                    lax.slice_in_dim(chunk, c * sub, (c + 1) * sub, axis=1),
                    axis_name,
                    sched.phase_permutation(k),
                )
                for c in range(num_chunks)
            ]
            got = jnp.concatenate(parts, axis=1)
        # The chunk I got came from `recv_from` and was destined for me.
        y = lax.dynamic_update_slice_in_dim(y, got, recv_from, axis=0)
    return y


def all_to_all(
    x: jax.Array,
    axis_name: str,
    impl: AllToAllImpl = "round_robin",
    num_chunks: int = 1,
) -> jax.Array:
    """Dispatcher: the communication multiplexer's shuffle entry point.

    ``num_chunks`` only affects the scheduled transports (the monolithic XLA
    all-to-all has no phases to pipeline).
    """
    if impl == "xla":
        return xla_all_to_all(x, axis_name)
    if impl == "round_robin":
        return scheduled_all_to_all(x, axis_name, schedule="shift", num_chunks=num_chunks)
    if impl == "one_factorization":
        return scheduled_all_to_all(
            x, axis_name, schedule="one_factorization", num_chunks=num_chunks
        )
    raise ValueError(f"unknown all_to_all impl {impl!r}")


def scheduled_all_to_all_consume(
    x: jax.Array,
    axis_name: str,
    consume: Callable[[Any, jax.Array, jax.Array], Any],
    init: Any,
    schedule: str = "shift",
) -> Any:
    """Streaming shuffle: fold each message as it arrives (paper §3.2 step 5-7).

    ``consume(acc, chunk, src_index) -> acc`` is applied to the device's own
    chunk first, then to each received chunk phase by phase.  Because the
    accumulator does not depend on later phases' sends, XLA can overlap the
    phase ``k+1`` ppermute with the phase ``k`` consume — the TPU analogue of
    the paper's multiplexer notifying workers to process messages right away
    instead of waiting for the full shuffle.  Avoids materializing the
    ``[n, ...]`` receive buffer (the message pool is one chunk deep).
    """
    n = lax.axis_size(axis_name)
    assert x.shape[0] == n
    me = lax.axis_index(axis_name)
    own = lax.dynamic_slice_in_dim(x, me, 1, axis=0)
    acc = consume(init, own[0], me)
    if n == 1:
        return acc
    sched = make_schedule(n, schedule)
    tgt_tab, src_tab = _phase_tables(sched)
    for k in range(sched.num_phases):
        send_to = tgt_tab[k, me]
        recv_from = src_tab[k, me]
        chunk = lax.dynamic_slice_in_dim(x, send_to, 1, axis=0)
        got = lax.ppermute(chunk, axis_name, sched.phase_permutation(k))
        acc = consume(acc, got[0], recv_from)
    return acc


# ----------------------------------------------------------------------------
# Broadcast exchange (paper §3.1: broadcast joins; §3.2.1 retain counter).
# ----------------------------------------------------------------------------

def ring_all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    """Broadcast exchange: every device ends with all ``n`` chunks.

    Ring algorithm = ``n - 1`` single-shift phases, each conflict-free; total
    volume per device is ``(n-1) * |x|`` — the hybrid model's "send once per
    remote server" (vs ``n*t - 1`` sends under classic exchange).  Result
    ``y[j]`` is device ``j``'s chunk.
    """
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    y = jnp.zeros((n,) + x.shape, x.dtype)
    y = lax.dynamic_update_slice_in_dim(y, x[None], me, axis=0)
    if n == 1:
        return y
    perm = [(i, (i + 1) % n) for i in range(n)]
    cur = x
    for k in range(1, n):
        cur = lax.ppermute(cur, axis_name, perm)
        src = (me - k) % n  # after k hops I hold device (me-k)'s chunk
        y = lax.dynamic_update_slice_in_dim(y, cur[None], src, axis=0)
    return y


def broadcast_exchange(x: jax.Array, axis_name: str, impl: str = "ring") -> jax.Array:
    if impl == "ring":
        return ring_all_gather(x, axis_name)
    if impl == "xla":
        return lax.all_gather(x, axis_name, axis=0, tiled=False)
    raise ValueError(f"unknown broadcast impl {impl!r}")


# ----------------------------------------------------------------------------
# Hierarchical collectives (hybrid parallelism for gradient sync).
# ----------------------------------------------------------------------------

def hierarchical_psum(x: jax.Array, inner_axis: str, outer_axis: str) -> jax.Array:
    """Two-level all-reduce: RS(inner) -> AR(outer) -> AG(inner).

    The paper's "network in the small vs in the large": the bandwidth-hungry
    reduce-scatter/all-gather stay on the fast inner network (ICI); only the
    already-reduced ``1/inner_size`` shard crosses the slow outer network
    (DCI).  Cross-pod traffic drops by the inner axis size versus a flat
    all-reduce over both axes.

    ``x``'s leading dim must be divisible by the inner axis size (use
    :func:`hierarchical_psum_tree` for arbitrary pytrees).
    """
    shard = lax.psum_scatter(x, inner_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, outer_axis)
    return lax.all_gather(shard, inner_axis, axis=0, tiled=True)


def _pad_to(x: jax.Array, multiple: int) -> jax.Array:
    pad = (-x.shape[0]) % multiple
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x


def hierarchical_psum_tree(tree: Any, inner_axis: str, outer_axis: str) -> Any:
    """Hierarchical all-reduce of a gradient pytree (flatten-pad-reshape)."""

    def one(leaf: jax.Array) -> jax.Array:
        flat = leaf.reshape(-1)
        n = flat.shape[0]
        inner = lax.axis_size(inner_axis)
        padded = _pad_to(flat, inner)
        red = hierarchical_psum(padded, inner_axis, outer_axis)
        return red[:n].reshape(leaf.shape)

    return jax.tree.map(one, tree)


def flat_psum_tree(tree: Any, axis_names: tuple[str, ...]) -> Any:
    """Baseline: single flat all-reduce over all data axes."""
    return jax.tree.map(lambda g: lax.psum(g, axis_names), tree)


# ----------------------------------------------------------------------------
# Hash shuffle: the decoupled exchange operator proper (paper §3.2 steps 1-7).
# ----------------------------------------------------------------------------

def fibonacci_hash(keys: jax.Array) -> jax.Array:
    """Schema-specialized hash of int keys (stands in for the paper's CRC32).

    The paper hashes join attributes with CRC32 (hardware instruction on
    x86).  TPUs have no CRC32 unit; a Fibonacci/murmur-style multiply-xor mix
    gives the same uniformity at pure-VPU cost.  Delegates to the single
    shared definition in :mod:`repro.kernels.ref` — the Pallas pack kernel
    uses the same one, which is what makes the xla/pallas pack paths
    bit-exact.
    """
    from repro.kernels.ref import fibonacci_hash_ref

    return fibonacci_hash_ref(keys)


def _scatter_pack(
    dest: jax.Array,
    my_rank: jax.Array,
    counts_all: jax.Array,
    rows: jax.Array,
    num_dest: int,
    capacity: int,
    valid: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Shared scatter epilogue: within-destination ranks -> message buffers.

    ``dest`` is the masked destination (invalid rows -> bin ``num_dest``),
    ``my_rank`` the arrival-order rank within that bin, ``counts_all`` the
    per-bin totals (only ``[:num_dest]`` is used).  The scatter itself stays
    in XLA — dynamic scatter is not an MXU shape.
    """
    counts = jnp.minimum(counts_all[:num_dest], capacity)
    keep = (my_rank < capacity) & valid & (dest < num_dest)
    slot = jnp.where(keep, dest * capacity + my_rank, num_dest * capacity)
    flat = jnp.zeros((num_dest * capacity + 1,) + rows.shape[1:], rows.dtype)
    flat = flat.at[slot].set(jnp.where(keep.reshape((-1,) + (1,) * (rows.ndim - 1)), rows, 0))
    buffers = flat[:-1].reshape((num_dest, capacity) + rows.shape[1:])
    dropped = (valid & (dest < num_dest)).sum() - keep.sum()
    return buffers, counts, dropped


def _rank_by_destination(
    dest: jax.Array, num_dest: int, impl: PackImpl
) -> tuple[jax.Array, jax.Array]:
    """Arrival-order rank within each destination bin + per-bin totals.

    ``dest`` must already have invalid rows masked to the overflow bin
    ``num_dest``.  Shared by :func:`pack_by_destination` and the two-level
    shuffle (which packs several arrays with one rank computation).
    """
    if impl == "pallas":
        from repro.kernels import ops as kernel_ops

        return kernel_ops.partition_ranks(dest, num_dest + 1)
    if impl == "xla":
        onehot = jax.nn.one_hot(dest, num_dest + 1, dtype=jnp.int32)
        rank = jnp.cumsum(onehot, axis=0) - onehot  # rank within destination
        my_rank = jnp.take_along_axis(rank, dest[:, None], axis=1)[:, 0]
        return my_rank, onehot.sum(axis=0)
    raise ValueError(f"unknown pack impl {impl!r}")


def pack_by_destination(
    dest: jax.Array,
    rows: jax.Array,
    num_dest: int,
    capacity: int,
    valid: jax.Array | None = None,
    impl: PackImpl = "xla",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partition ``rows`` into per-destination message buffers (paper step 2).

    Returns ``(buffers, counts, dropped)`` with ``buffers: [num_dest,
    capacity, row...]``, ``counts: [num_dest]`` valid rows per buffer and
    ``dropped``: rows lost to capacity overflow (0 when capacity is sized to
    the skew bound).  Static shapes throughout — the message pool analogue:
    fixed-size reusable buffers.

    ``impl="xla"`` ranks rows with a ``[rows, num_dest + 1]`` one-hot/cumsum
    (the reference); ``impl="pallas"`` uses the fused block-parallel kernel
    (:func:`repro.kernels.ops.partition_ranks`) and never materializes the
    one-hot.  Both produce bit-identical buffers, counts and drop counts.
    """
    nrows = dest.shape[0]
    if valid is None:
        valid = jnp.ones((nrows,), jnp.bool_)
    dest = jnp.where(valid, dest, num_dest)  # invalid rows -> overflow bucket
    my_rank, counts_all = _rank_by_destination(dest, num_dest, impl)
    return _scatter_pack(dest, my_rank, counts_all, rows, num_dest, capacity, valid)


def hash_shuffle(
    keys: jax.Array,
    rows: jax.Array,
    axis_name: str,
    capacity: int,
    impl: AllToAllImpl = "round_robin",
    valid: jax.Array | None = None,
    pack_impl: PackImpl = "xla",
    num_chunks: int = 1,
    transport_chunks: int = 1,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full decoupled exchange: partition by key hash, shuffle, reassemble.

    Per device: rows whose ``hash(key) % n == j`` are packed into message
    ``j`` and shuffled so that afterwards every device holds exactly the rows
    hashing to its index.  Returns ``(rows_out, valid_out, dropped)`` where
    ``rows_out: [n * capacity, row...]`` and ``valid_out`` masks real rows.

    ``pack_impl="pallas"`` fuses hash + mask + rank into one kernel pass
    (:func:`repro.kernels.ops.hash_partition_ranks`).

    ``num_chunks > 1`` turns the shuffle into a chunked double-buffered
    pipeline: rows are split into ``num_chunks`` equal chunks (each with
    ``capacity / num_chunks`` per-destination slots), and chunk ``k + 1`` is
    packed *before* chunk ``k``'s phases are issued, so partition compute
    overlaps shuffle DMA.  Requires ``num_chunks`` to divide both the row
    count and ``capacity``.  The output layout is unchanged
    (``rows_out[j*capacity : (j+1)*capacity]`` holds device ``j``'s rows in
    arrival order), but padding slots sit at each chunk boundary rather than
    all at the tail, and capacity overflow is assessed per chunk.

    ``transport_chunks`` is forwarded to the scheduled transports: each
    phase's message buffer is split into this many independent ppermutes
    (must divide the per-chunk capacity; the tiny counts exchange is never
    split).
    """
    n = lax.axis_size(axis_name)
    T = keys.shape[0]
    if valid is None:
        valid = jnp.ones((T,), jnp.bool_)
    assert T % num_chunks == 0 and capacity % num_chunks == 0, (
        f"num_chunks={num_chunks} must divide rows={T} and capacity={capacity}"
    )
    cap_c = capacity // num_chunks
    assert cap_c % transport_chunks == 0, (
        f"transport_chunks={transport_chunks} must divide per-chunk capacity {cap_c}"
    )
    rows_c = T // num_chunks

    def pack(c: int):
        sl = slice(c * rows_c, (c + 1) * rows_c)
        keys_c, data_c, valid_c = keys[sl], rows[sl], valid[sl]
        if pack_impl == "pallas":
            from repro.kernels import ops as kernel_ops

            dest, my_rank, counts_all = kernel_ops.hash_partition_ranks(
                keys_c, valid_c.astype(jnp.int32), n
            )
            return _scatter_pack(dest, my_rank, counts_all, data_c, n, cap_c, valid_c)
        dest = (fibonacci_hash(keys_c) % jnp.uint32(n)).astype(jnp.int32)
        return pack_by_destination(dest, data_c, n, cap_c, valid=valid_c, impl=pack_impl)

    # Double-buffered pipeline: the pack of chunk c+1 is issued before the
    # ppermute phases of chunk c and has no data dependence on them, so the
    # async scheduler is free to overlap partition compute with shuffle DMA.
    packed = pack(0)
    shuffled_chunks, counts_chunks = [], []
    dropped = jnp.int32(0)
    for c in range(num_chunks):
        bufs, counts, dropped_c = packed
        if c + 1 < num_chunks:
            packed = pack(c + 1)
        shuffled_chunks.append(
            all_to_all(bufs, axis_name, impl=impl, num_chunks=transport_chunks)
        )
        counts_chunks.append(
            all_to_all(counts.reshape(n, 1), axis_name, impl=impl).reshape(n)
        )
        dropped = dropped + dropped_c

    if num_chunks == 1:
        shuffled, counts_in = shuffled_chunks[0], counts_chunks[0]
        rows_out = shuffled.reshape((n * capacity,) + shuffled.shape[2:])
        valid_out = (
            jnp.arange(cap_c)[None, :] < counts_in[:, None]
        ).reshape(n * capacity)
    else:
        stacked = jnp.stack(shuffled_chunks, axis=1)  # [n, C, cap_c, row...]
        rows_out = stacked.reshape((n * capacity,) + stacked.shape[3:])
        counts_in = jnp.stack(counts_chunks, axis=1)  # [n, C]
        valid_out = (
            jnp.arange(cap_c)[None, None, :] < counts_in[:, :, None]
        ).reshape(n * capacity)
    return rows_out, valid_out, lax.psum(dropped, axis_name)


def hash_shuffle_spill(
    keys: jax.Array,
    rows: jax.Array,
    axis_name: str,
    capacity: int,
    impl: AllToAllImpl = "round_robin",
    valid: jax.Array | None = None,
    pack_impl: PackImpl = "xla",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Exchange that reports overflow instead of dropping it.

    Same wire layout as single-chunk :func:`hash_shuffle`, but a row whose
    within-destination arrival rank exceeds ``capacity`` is *withheld on the
    sender* rather than silently lost: the third return value is a per-row
    boolean ``spilled`` mask (sender-local, shape ``[rows]``).  The caller
    moves the masked rows to a host-memory overflow partition and re-offers
    them in a later drain pass.  Delivered rows are structurally drop-free —
    every row is either in ``rows_out`` on its owner or flagged in
    ``spilled`` on its sender, never neither.

    Overflow is detectable before any data moves because the rank/count pass
    runs on the sender (paper §3.2 step 2): ``my_rank >= capacity`` is
    exactly the overflow condition the fixed-size message pool would hit.
    """
    n = lax.axis_size(axis_name)
    T = keys.shape[0]
    if valid is None:
        valid = jnp.ones((T,), jnp.bool_)
    if pack_impl == "pallas":
        from repro.kernels import ops as kernel_ops

        dest, my_rank, counts_all = kernel_ops.hash_partition_ranks(
            keys, valid.astype(jnp.int32), n
        )
    else:
        dest = (fibonacci_hash(keys) % jnp.uint32(n)).astype(jnp.int32)
        dest = jnp.where(valid, dest, n)
        my_rank, counts_all = _rank_by_destination(dest, n, pack_impl)
    spilled = valid & (my_rank >= capacity)
    deliver = valid & ~spilled
    bufs, counts, _ = _scatter_pack(dest, my_rank, counts_all, rows, n, capacity, deliver)
    shuffled = all_to_all(bufs, axis_name, impl=impl)
    counts_in = all_to_all(counts.reshape(n, 1), axis_name, impl=impl).reshape(n)
    rows_out = shuffled.reshape((n * capacity,) + shuffled.shape[2:])
    valid_out = (jnp.arange(capacity)[None, :] < counts_in[:, None]).reshape(n * capacity)
    return rows_out, valid_out, spilled


# ----------------------------------------------------------------------------
# Two-level exchange: coarse cross-pod hop + fine in-pod shuffle (paper §3.1).
# ----------------------------------------------------------------------------

def hash_shuffle_two_level(
    keys: jax.Array,
    rows: jax.Array,
    inner_axis: str,
    outer_axis: str,
    capacity: int,
    impl: AllToAllImpl = "round_robin",
    valid: jax.Array | None = None,
    pack_impl: PackImpl = "xla",
    num_chunks: int = 1,
    transport_chunks: int = 1,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Globally repartition by key hash over a two-level (pod x inner) mesh.

    The paper's hybrid-parallelism rule says fine-grained shuffles must never
    cross the network in the large — but a join still needs rows with equal
    keys co-located *globally*.  The resolution (§3.1/§3.2.2) is granularity:
    the slow network carries one COARSE message per remote server pair
    (multiplexer-to-multiplexer), while fine-grained partitioning stays on
    the fast network.  This is that exchange, as two hops:

    1. **cross-pod, coarse** — rows are packed by *destination pod*
       (``hash(key) % (P * n) // n``) and shipped over ``outer_axis`` with
       one message per peer pod.  Per device that is ``P - 1`` messages of
       up to the full local row count — pod granularity, so the cross-DCI
       connection count is ``N * (P - 1)`` instead of the classic
       ``N * (N - 1)`` (the paper's ``n^2`` vs ``n^2 t^2`` argument).
    2. **in-pod, fine** — a normal :func:`hash_shuffle` over ``inner_axis``
       delivers each row to the in-pod device owning ``hash(key) % n``
       (because ``n`` divides ``P * n``, the in-pod owner is independent of
       which pod computed it).

    The destination device for every row is exactly the one a flat
    ``hash % N`` shuffle over the joint axis would pick (mesh device order
    puts pod ``p``'s devices at indices ``p*n .. p*n + n - 1``), so results
    match the single-level exchange up to arrival order.

    ``capacity`` has flat-shuffle semantics: the per-(src, dst) message
    bound of the equivalent *global* exchange.  The output is
    ``[n * P * capacity]`` rows per device — the same total as a flat
    ``N``-unit shuffle with that capacity.  Hop 1 is structurally zero-drop
    (its per-pod message capacity is the full local row count); hop 2
    inherits the caller's bound scaled by ``P``.  ``num_chunks`` /
    ``transport_chunks`` pipeline the in-pod hop (the coarse hop is a single
    phase sequence and ships unchunked).  The returned ``dropped`` is
    psummed over BOTH axes — a global count.
    """
    P = lax.axis_size(outer_axis)
    if P == 1:
        out_rows, out_valid, dropped = hash_shuffle(
            keys, rows, inner_axis, capacity, impl=impl, valid=valid,
            pack_impl=pack_impl, num_chunks=num_chunks,
            transport_chunks=transport_chunks,
        )
        return out_rows, out_valid, lax.psum(dropped, outer_axis)
    n = lax.axis_size(inner_axis)
    N = P * n
    T = keys.shape[0]
    if valid is None:
        valid = jnp.ones((T,), jnp.bool_)

    # Hop 1: pack by destination pod, one rank computation for keys + rows.
    gdest = (fibonacci_hash(keys) % jnp.uint32(N)).astype(jnp.int32)
    dest_pod = jnp.where(valid, gdest // n, P)  # invalid -> overflow bucket
    my_rank, counts_all = _rank_by_destination(dest_pod, P, pack_impl)
    # Coarse shift phases over the pod axis (the multiplexer connections of
    # the paper): scheduled transports use the shift schedule — valid for
    # every P, unlike one_factorization — and "xla" keeps the monolithic
    # all-to-all for the baseline configuration.
    hop1 = "xla" if impl == "xla" else "round_robin"
    if rows.ndim == 2 and rows.dtype == keys.dtype:
        # Ship keys as an extra leading column of the row matrix: one phase
        # sequence over the slowest network instead of two.  (This is the
        # relational hot path — int32 keys, packed int32 rows.)
        aug = jnp.concatenate([keys[:, None], rows], axis=1)
        aug_bufs, counts, drop1 = _scatter_pack(
            dest_pod, my_rank, counts_all, aug, P, T, valid
        )
        aug_in = all_to_all(aug_bufs, outer_axis, impl=hop1)
        keys_in, rows_in = aug_in[:, :, 0], aug_in[:, :, 1:]
    else:
        key_bufs, counts, drop1 = _scatter_pack(
            dest_pod, my_rank, counts_all, keys, P, T, valid
        )
        row_bufs, _, _ = _scatter_pack(
            dest_pod, my_rank, counts_all, rows, P, T, valid
        )
        keys_in = all_to_all(key_bufs, outer_axis, impl=hop1)
        rows_in = all_to_all(row_bufs, outer_axis, impl=hop1)
    counts_in = all_to_all(counts.reshape(P, 1), outer_axis, impl=hop1)
    valid_in = (
        jnp.arange(T)[None, :] < counts_in.reshape(P)[:, None]
    ).reshape(P * T)

    # Hop 2: ordinary in-pod shuffle.  n | N makes hash % n the correct
    # in-pod owner for rows from any source pod.
    out_rows, out_valid, drop2 = hash_shuffle(
        keys_in.reshape(P * T),
        rows_in.reshape((P * T,) + rows_in.shape[2:]),
        inner_axis,
        capacity * P,
        impl=impl,
        valid=valid_in,
        pack_impl=pack_impl,
        num_chunks=num_chunks,
        transport_chunks=transport_chunks,
    )
    # drop2 is already psummed over the inner axis; lift both to global.
    dropped = lax.psum(lax.psum(drop1, inner_axis), outer_axis)
    dropped = dropped + lax.psum(drop2, outer_axis)
    return out_rows, out_valid, dropped


# ----------------------------------------------------------------------------
# Generic two-level dispatch/combine: the token-routing fabric (paper §3.1).
# ----------------------------------------------------------------------------

def _hop1_impl(impl: AllToAllImpl) -> AllToAllImpl:
    """Coarse-hop transport: shift phases are valid for every pod count
    (one_factorization needs even n), xla keeps the monolithic baseline."""
    return "xla" if impl == "xla" else "round_robin"


def dispatch_two_level(
    x: jax.Array,
    inner_axis: str,
    outer_axis: str,
    impl: AllToAllImpl = "round_robin",
    num_chunks: int = 1,
) -> jax.Array:
    """All-to-all over the JOINT ``(outer, inner)`` axis, as two hops.

    ``x[q * n + j]`` (leading dim ``N = P * n``, mesh device order
    ``(pod, inner) -> pod * n + inner``) is the chunk destined for pod ``q``'s
    device ``j``; the result's entry ``q * n + j`` is the chunk received from
    that device — exactly the contract of a flat :func:`all_to_all` over the
    joint axis, but routed hierarchically:

    1. **coarse, cross-pod** — ``x`` is regrouped by destination *pod* and
       shipped over ``outer_axis`` with ONE message per peer pod (the
       paper's multiplexer-to-multiplexer connection over the network in
       the large: ``P - 1`` coarse messages instead of ``N - 1`` fine ones).
    2. **fine, in-pod** — a scheduled all-to-all over ``inner_axis``
       delivers each sub-chunk to its in-pod owner (``num_chunks`` is the
       transport sub-chunking of this hop).

    Both hops are pure permutations of the same elements — zero arithmetic —
    so the result is BIT-IDENTICAL to the flat joint-axis all-to-all for
    every dtype.  This is what lets MoE expert dispatch (and any other
    token-routing workload) cross a pod mesh without a correctness caveat.

    Generalizes :func:`hash_shuffle_two_level` beyond hash keys: here the
    caller has already assigned every row a destination slot (the leading
    index); the two-level route only changes *how* the bytes move.
    """
    P = lax.axis_size(outer_axis)
    n = lax.axis_size(inner_axis)
    if P == 1:
        return all_to_all(x, inner_axis, impl=impl, num_chunks=num_chunks)
    N = P * n
    assert x.shape[0] == N, (
        f"leading dim {x.shape[0]} != joint axis size {P} * {n}"
    )
    rest = x.shape[1:]
    # Hop 1 (coarse): x3[q] = everything destined for pod q, contiguous.
    x3 = x.reshape((P, n) + rest)
    h = all_to_all(x3, outer_axis, impl=_hop1_impl(impl))
    # h[q, j] = chunk from pod q (same inner index) destined for (my_pod, j).
    h2 = jnp.swapaxes(h, 0, 1).reshape((n, -1))
    # Hop 2 (fine): deliver to the in-pod owner j.
    g = all_to_all(h2, inner_axis, impl=impl, num_chunks=num_chunks)
    # g[j, q] = chunk from (q, j) destined for me; restore flat (q, j) order.
    out = jnp.swapaxes(g.reshape((n, P) + rest), 0, 1)
    return out.reshape((N,) + rest)


def combine_two_level(
    x: jax.Array,
    inner_axis: str,
    outer_axis: str,
    impl: AllToAllImpl = "round_robin",
    num_chunks: int = 1,
) -> jax.Array:
    """The return trip of :func:`dispatch_two_level` (same flat-all-to-all
    contract), with the hop order mirrored: fine in-pod first, then ONE
    coarse message per peer pod over ``outer_axis``.  Also a pure
    permutation — bit-identical to the flat route."""
    P = lax.axis_size(outer_axis)
    n = lax.axis_size(inner_axis)
    if P == 1:
        return all_to_all(x, inner_axis, impl=impl, num_chunks=num_chunks)
    N = P * n
    assert x.shape[0] == N, (
        f"leading dim {x.shape[0]} != joint axis size {P} * {n}"
    )
    rest = x.shape[1:]
    # Hop 1 (fine): group by destination inner index, shuffle in-pod.
    x3 = jnp.swapaxes(x.reshape((P, n) + rest), 0, 1).reshape((n, -1))
    g = all_to_all(x3, inner_axis, impl=impl, num_chunks=num_chunks)
    # g[j, q] -> h[q, j]: everything destined for pod q, contiguous again.
    h = jnp.swapaxes(g.reshape((n, P) + rest), 0, 1)
    # Hop 2 (coarse): one message per peer pod over the slow network.
    out3 = all_to_all(h, outer_axis, impl=_hop1_impl(impl))
    return out3.reshape((N,) + rest)


__all__ = [
    "AllToAllImpl",
    "PackImpl",
    "xla_all_to_all",
    "scheduled_all_to_all",
    "scheduled_all_to_all_consume",
    "all_to_all",
    "ring_all_gather",
    "broadcast_exchange",
    "hierarchical_psum",
    "hierarchical_psum_tree",
    "flat_psum_tree",
    "fibonacci_hash",
    "pack_by_destination",
    "hash_shuffle",
    "hash_shuffle_spill",
    "hash_shuffle_two_level",
    "dispatch_two_level",
    "combine_two_level",
]
