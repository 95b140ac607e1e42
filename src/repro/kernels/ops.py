"""Jit'd public wrappers: kernel when eligible, reference otherwise.

``use_kernels(False)`` (or the REPRO_NO_KERNELS env var) forces the jnp
reference everywhere — the A/B switch the tests and benchmarks flip.
On CPU the kernels execute via ``interpret=True``; on TPU the same code
compiles natively (interpret flag keys off the backend).
"""

from __future__ import annotations

import contextlib
import os
from functools import partial

import jax
import jax.numpy as jnp

from . import ref as R

_FORCE_REF = os.environ.get("REPRO_NO_KERNELS", "") not in ("", "0")
_STATE = {"enabled": not _FORCE_REF}


def kernels_enabled() -> bool:
    return _STATE["enabled"]


@contextlib.contextmanager
def use_kernels(enabled: bool):
    prev = _STATE["enabled"]
    _STATE["enabled"] = enabled
    try:
        yield
    finally:
        _STATE["enabled"] = prev


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ----------------------------------------------------------------------------

def flash_attention(q, k, v, causal=True, scale=None):
    """q [B,S,H,D] model layout -> kernel layout [B,H,S,D] and back."""
    B, Sq, H, D = q.shape
    ok = (
        kernels_enabled()
        and Sq % 128 == 0
        and k.shape[1] % 128 == 0
        and D in (32, 64, 128, 256)
        and H % k.shape[2] == 0
    )
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if ok:
        from .flash_attention import flash_attention as kern

        out = kern(qt, kt, vt, causal=causal, scale=scale, interpret=_interpret())
    else:
        out = R.flash_attention_ref(qt, kt, vt, causal=causal, scale=scale)
    return out.transpose(0, 2, 1, 3)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention_vjp(q, k, v, causal=True):
    """Differentiable flash attention: Pallas forward, chunked-jnp backward.

    The backward recomputes attention with the query-chunked reference and
    differentiates that — flash-style memory without a handwritten backward
    kernel (the recompute is what a remat'd sdpa would do anyway).
    """
    return flash_attention(q, k, v, causal=causal)


def _fa_fwd(q, k, v, causal):
    return flash_attention(q, k, v, causal=causal), (q, k, v)


def _fa_bwd(causal, res, g):
    q, k, v = res
    from repro.models.layers import chunked_sdpa

    def f(q, k, v):
        return chunked_sdpa(q, k, v, causal=causal, q_block=min(512, q.shape[1]))

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_attention_vjp.defvjp(_fa_fwd, _fa_bwd)


def ssd_scan(x, dt, A, Bm, Cm, chunk, initial_state=None):
    ok = (
        kernels_enabled()
        and Bm.shape[2] == 1
        and x.shape[1] % chunk == 0
        and x.shape[2] % min(8, x.shape[2]) == 0
    )
    if ok:
        from .ssd_scan import ssd_scan as kern

        return kern(x, dt, A, Bm, Cm, chunk, initial_state, interpret=_interpret())
    return R.ssd_scan_ref(x, dt, A, Bm, Cm, chunk, initial_state)


def hash_partition(keys, num_partitions, block=256):
    T = keys.shape[0]
    blk = min(block, T)
    if kernels_enabled() and T % blk == 0 and blk % 128 == 0:
        from .hash_partition import hash_partition as kern

        return kern(keys, num_partitions, block=blk, interpret=_interpret())
    return R.hash_partition_ref(keys, num_partitions, block=blk)


def _combine_block_ranks(hist, local_rank, dest, blk):
    """Global within-bin ranks from per-block histograms + block-local ranks.

    ``rank[t] = sum(hist[b, dest[t]] for b < block_of(t)) + local_rank[t]``;
    the exclusive scan is over ``[nblocks, num_bins]`` and the per-row lookup
    is a flat 1-D gather — nothing of shape ``[rows, num_bins]`` exists.
    """
    num_bins = hist.shape[1]
    base = jnp.cumsum(hist, axis=0) - hist  # exclusive over blocks
    blocks = jnp.arange(dest.shape[0]) // blk
    flat_idx = blocks * num_bins + jnp.clip(dest, 0, num_bins - 1)
    return base.reshape(-1)[flat_idx] + local_rank


def partition_ranks(dest, num_bins, block=256):
    """(within-bin ranks [T], bin counts [num_bins]) for destination ids.

    The fused-pack entry point: Pallas kernel per block (histogram +
    block-local rank), cheap XLA combine across blocks.  ``dest`` values
    outside ``[0, num_bins)`` get an arbitrary rank and count nowhere.
    Handles arbitrary ``T`` by padding with an inert out-of-range id to a
    whole number of blocks (the kernel's lane-aligned tiling).
    """
    T = dest.shape[0]
    pad = (-T) % block
    d = dest.astype(jnp.int32)
    if pad:
        d = jnp.concatenate([d, jnp.full((pad,), num_bins, jnp.int32)])
    if kernels_enabled():
        from .hash_partition import partition_pack as kern

        hist, local = kern(d, num_bins, block=block, interpret=_interpret())
    else:
        hist, local = R.partition_pack_ref(d, num_bins, block=block)
    rank = _combine_block_ranks(hist, local, d, block)
    return rank[:T], hist.sum(axis=0)


def hash_partition_ranks(keys, valid, num_partitions, block=256):
    """Fused hash+mask+rank: (dest [T], ranks [T], counts [P+1]).

    ``dest`` is the masked destination (invalid rows -> overflow bin ``P``).
    Padding rows (arbitrary ``T``) land in the overflow bin, so
    ``counts[num_partitions]`` includes them — only ``counts[:P]`` is
    meaningful to callers.
    """
    T = keys.shape[0]
    pad = (-T) % block
    k = keys.astype(jnp.int32)
    v = valid.astype(jnp.int32)
    if pad:
        k = jnp.concatenate([k, jnp.zeros((pad,), jnp.int32)])
        v = jnp.concatenate([v, jnp.zeros((pad,), jnp.int32)])
    if kernels_enabled():
        from .hash_partition import hash_partition_pack as kern

        dest, hist, local = kern(
            k, v, num_partitions, block=block, interpret=_interpret()
        )
    else:
        dest, hist, local = R.hash_partition_pack_ref(k, v, num_partitions, block=block)
    rank = _combine_block_ranks(hist, local, dest, block)
    return dest[:T], rank[:T], hist.sum(axis=0)


def moe_dispatch(dest, num_dest, capacity, block=256):
    """(slot [T], counts [num_dest]); overflow/padding -> num_dest*capacity.

    Arbitrary ``T``: the kernel pads with an inert id to whole blocks — the
    decode-step dispatch ships a handful of tokens per slot, far from any
    block multiple.
    """
    if kernels_enabled():
        from .moe_dispatch import moe_dispatch as kern

        return kern(dest, num_dest, capacity, block=block, interpret=_interpret())
    return R.moe_dispatch_ref(dest, num_dest, capacity)


__all__ = [
    "kernels_enabled",
    "use_kernels",
    "flash_attention",
    "ssd_scan",
    "hash_partition",
    "partition_ranks",
    "hash_partition_ranks",
    "moe_dispatch",
]
