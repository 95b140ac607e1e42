"""Relational operators with static shapes (the local execution engine).

Everything is mask-carrying and shape-static so it jits, shards, and lowers
for the dry-run.  The operators mirror HyPer's pipeline set used by the
paper's TPC-H plans: filter (selection vectors), project (column pruning),
group-by aggregation, PK-FK join, top-k.

HARDWARE ADAPTATION (DESIGN.md §2): HyPer's joins/aggregations are
hash-table-based — pointer chasing that x86 cores love and TPU vector units
hate.  The TPU-idiomatic equivalents used here are *sort-based*: bitonic
sort + ``searchsorted`` for PK-FK joins and sorted segment reduction for
group-by.  Same results, same asymptotics up to the log factor, but contiguous
vector memory traffic instead of random probes.  (The paper itself cites
MPSM [2] — sort-merge — as the NUMA-friendly choice; the same argument holds
one level down on the TPU.)  The *distributed* layer on top (queries.py) is
exactly the paper's: partition/broadcast decisions + the scheduled exchange.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from .table import Table

_KEY_SENTINEL = jnp.iinfo(jnp.int32).max


# ----------------------------------------------------------------------------
# Aggregation primitives.
# ----------------------------------------------------------------------------

def sum_where(col: jax.Array, mask: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Masked sum.  Money/quantity sums accumulate in f32: int32 would
    overflow on TPC-H money columns and int64/f64 need the global x64 flag.
    Two-stage (per-device then psum) summation keeps the f32 error ~1e-6."""
    return jnp.sum(jnp.where(mask, col.astype(dtype), 0))


def count_where(mask: jax.Array) -> jax.Array:
    return jnp.sum(mask.astype(jnp.int32))


# ----------------------------------------------------------------------------
# Group-by: dense (small key domain) and sort-based (large key domain).
# ----------------------------------------------------------------------------

def groupby_dense(
    group_ids: jax.Array,
    num_groups: int,
    aggregates: dict[str, tuple[jax.Array, str]],
    valid: jax.Array,
) -> dict[str, jax.Array]:
    """Aggregate into a small dense group table (e.g. Q1's 6 groups).

    ``aggregates``: name -> (column, 'sum'|'count').  This is the paper's
    *pre-aggregation* building block (Fig 6c): each device reduces its rows
    locally into num_groups cells; cross-device combination is a psum of the
    tiny group table instead of a shuffle of raw rows.
    """
    gid = jnp.where(valid, group_ids, num_groups)  # invalid -> overflow cell
    out = {}
    for name, (col, kind) in aggregates.items():
        if kind == "sum":
            vals = col.astype(jnp.float32)
        else:  # count
            vals = jnp.ones_like(gid, jnp.int32)
        out[name] = _blocked_segment_sum(
            jnp.where(valid, vals, 0), gid, num_groups + 1
        )[:num_groups]
    return out


# Rows per partial sum of the dense group-by.  A scatter may add a group's
# rows in row order into one f32 accumulator: over TPC-H SF 1 (1M rows per
# Q1 group) that drifts past 1e-4 relative.  Per-block partials, combined
# by one reduction, keep every accumulator to a few thousand rows.
SUM_BLOCK = 4096


def _blocked_segment_sum(
    vals: jax.Array, gid: jax.Array, num_segments: int
) -> jax.Array:
    """``segment_sum`` as per-block partial sums, then one reduction."""
    pad = (-vals.shape[0]) % SUM_BLOCK
    vals = jnp.pad(vals, (0, pad)).reshape(-1, SUM_BLOCK)
    gid = jnp.pad(gid, (0, pad)).reshape(-1, SUM_BLOCK)
    partial = jax.vmap(
        lambda v, g: jax.ops.segment_sum(v, g, num_segments=num_segments)
    )(vals, gid)
    return partial.sum(axis=0)


def groupby_sorted(
    keys: jax.Array,
    valid: jax.Array,
    aggregates: dict[str, tuple[jax.Array, str]],
) -> tuple[jax.Array, jax.Array, dict[str, jax.Array]]:
    """Sort-based group-by for large key domains (e.g. Q3's orderkeys).

    Returns ``(group_keys, group_valid, aggs)`` all with the input's
    capacity (each row could be its own group — the static worst case).
    """
    n = keys.shape[0]
    skeys = jnp.where(valid, keys.astype(jnp.int32), _KEY_SENTINEL)
    order = jnp.argsort(skeys)
    sk = skeys[order]
    is_start = jnp.concatenate([jnp.ones((1,), jnp.bool_), sk[1:] != sk[:-1]])
    gid = jnp.cumsum(is_start) - 1  # dense group id per sorted row
    sval = valid[order]
    out = {}
    for name, (col, kind) in aggregates.items():
        vals = (
            col.astype(jnp.float32)[order]
            if kind == "sum"
            else jnp.ones((n,), jnp.int32)
        )
        out[name] = jax.ops.segment_sum(
            jnp.where(sval, vals, 0), gid, num_segments=n
        )
    gkeys = jax.ops.segment_max(
        jnp.where(sval, sk, -1), gid, num_segments=n
    )
    gvalid = (
        jax.ops.segment_max(sval.astype(jnp.int32), gid, num_segments=n) > 0
    )
    return gkeys, gvalid, out


# ----------------------------------------------------------------------------
# PK-FK join (build side has unique keys — every TPC-H join in our plans).
# ----------------------------------------------------------------------------

def join_pk(
    build_keys: jax.Array,
    build_valid: jax.Array,
    probe_keys: jax.Array,
    probe_valid: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Sorted PK-FK join: returns (build_row_index, match_mask) per probe row.

    Build side is sorted once (invalid keys to +inf), probes binary-search it.
    ``build_row_index`` addresses the ORIGINAL build table order, so callers
    gather payload columns directly.
    """
    skeys = jnp.where(build_valid, build_keys.astype(jnp.int32), _KEY_SENTINEL)
    order = jnp.argsort(skeys)
    sk = skeys[order]
    pos = jnp.searchsorted(sk, probe_keys.astype(jnp.int32))
    pos = jnp.clip(pos, 0, sk.shape[0] - 1)
    match = (sk[pos] == probe_keys.astype(jnp.int32)) & probe_valid
    return order[pos], match


def gather_payload(
    build: Table, build_idx: jax.Array, match: jax.Array, names: list[str]
) -> dict[str, jax.Array]:
    """Gather build-side columns for matched probe rows (zeros elsewhere)."""
    out = {}
    for n in names:
        col = build.columns[n]
        got = col[build_idx]
        out[n] = jnp.where(match, got, jnp.zeros_like(got))
    return out


# ----------------------------------------------------------------------------
# Top-k (Q3's ORDER BY revenue DESC LIMIT 10).
# ----------------------------------------------------------------------------

def topk_rows(
    sort_key: jax.Array, valid: jax.Array, k: int, payload: dict[str, jax.Array]
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Top-k rows by key (descending); invalid rows sort last."""
    neg = jnp.where(valid, sort_key.astype(jnp.float32), -jnp.inf)
    vals, idx = jax.lax.top_k(neg, k)
    out = {name: col[idx] for name, col in payload.items()}
    return vals, out


# ----------------------------------------------------------------------------
# Decimal helpers (money is int64 cents; percents are int 0..100).
# ----------------------------------------------------------------------------

def money_times_pct(money: jax.Array, pct: jax.Array) -> jax.Array:
    """money * (pct/100) in f32 (cents scale; see sum_where dtype note)."""
    return money.astype(jnp.float32) * (pct.astype(jnp.float32) / 100.0)


__all__ = [
    "sum_where",
    "count_where",
    "groupby_dense",
    "groupby_sorted",
    "join_pk",
    "gather_payload",
    "topk_rows",
    "money_times_pct",
]
