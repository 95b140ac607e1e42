"""Mixture-of-Experts layer routed through the paper's exchange machinery.

The mapping (DESIGN.md §4): a token is a *tuple*, the router's expert id is
the *join key*, per-expert capacity buffers are the *message pool*, and the
expert-parallel dispatch/combine is the decoupled exchange operator's
all-to-all — executed by :func:`repro.core.exchange.all_to_all` with either
the paper's round-robin phase schedule or XLA's monolithic collective
(``cfg.exchange_impl``).

Three execution paths (``cfg.moe_impl``):

* ``"dense"``  — every device evaluates every expert, weighted combine.
  Exact (no capacity drops); used for CPU smoke tests and as the oracle in
  property tests.  With ``experts -> model`` sharding constraints this is
  also the efficient *decode* path (few tokens, replicate-and-reduce), so
  ``"gspmd"`` is an alias.
* ``"ep_shardmap"`` — true expert parallelism: tokens are sharded over the
  exchange axis, packed into per-expert capacity buffers, shuffled to the
  expert owners (scheduled all-to-all), batch-matmul'd, shuffled back, and
  combined.  This is the paper's §3.2 pipeline, steps 1-7.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import exchange
from repro.distributed.sharding import current_mesh_context
from . import layers as L


# ----------------------------------------------------------------------------
# Params.
# ----------------------------------------------------------------------------

def init_moe_layer(key, cfg: ModelConfig) -> Any:
    d, E, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff or cfg.d_ff
    dt = L.pdtype(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "router": L._normal(ks[0], (d, E), 0.02, jnp.float32),  # router in f32
        "w_gate": L.he_init(ks[1], (E, d, f), d, dt),
        "w_up": L.he_init(ks[2], (E, d, f), d, dt),
        "w_down": L.he_init(ks[3], (E, f, d), f, dt),
    }
    if cfg.num_shared_experts:
        p["shared"] = L.init_mlp(ks[4], cfg, d_ff=f * cfg.num_shared_experts)
    return p


def specs_moe_layer(cfg: ModelConfig) -> Any:
    s = {
        "router": (None, None),
        "w_gate": ("experts", "expert_fsdp", None),
        "w_up": ("experts", "expert_fsdp", None),
        "w_down": ("experts", None, "expert_fsdp"),
    }
    if cfg.num_shared_experts:
        s["shared"] = L.specs_mlp(cfg)
    return s


# ----------------------------------------------------------------------------
# Router.
# ----------------------------------------------------------------------------

def route(params, cfg: ModelConfig, x: jax.Array):
    """Top-k routing -> (weights [T, k] f32, expert ids [T, k] int32)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, cfg.top_k)
    if cfg.router_norm_topk:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, idx.astype(jnp.int32)


def _expert_ffn(w_gate, w_up, w_down, x):
    """Batched per-expert SwiGLU: x [E, C, d] -> [E, C, d]."""
    g = jnp.einsum("ecd,edf->ecf", x, w_gate)
    u = jnp.einsum("ecd,edf->ecf", x, w_up)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down)


# ----------------------------------------------------------------------------
# Dense / GSPMD path (exact; smoke oracle; decode).
# ----------------------------------------------------------------------------

def moe_dense(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Evaluate all experts for all tokens, combine by router weight.

    With ``experts -> model`` sharding the per-expert compute is model-
    parallel and the weighted sum contracts the expert dim (XLA inserts the
    reduce) — the standard replicate-tokens EP used at decode.
    """
    T, d = x.shape
    dt = x.dtype
    w, idx = route(params, cfg, x)
    # full [T, E] combine weights (zero where not selected)
    full_w = jnp.zeros((T, cfg.num_experts), jnp.float32)
    full_w = jax.vmap(lambda fw, ww, ii: fw.at[ii].add(ww))(full_w, w, idx)
    g = jnp.einsum("td,edf->tef", x, params["w_gate"].astype(dt))
    u = jnp.einsum("td,edf->tef", x, params["w_up"].astype(dt))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("tef,efd->ted", h, params["w_down"].astype(dt))
    return jnp.einsum("ted,te->td", y, full_w.astype(dt))


# ----------------------------------------------------------------------------
# Expert-parallel shard_map path (the paper's exchange pipeline).
# ----------------------------------------------------------------------------

def _ep_capacity(cfg: ModelConfig, tokens_per_shard: int, num_shards: int) -> int:
    """Per-expert message-buffer capacity (paper: fixed-size reusable pool).

    Delegates to :func:`repro.core.autotune.ep_capacity` — the ONE place the
    formula lives, so the tuner's decode-shaped pricing can never drift from
    the buffers this layer actually ships.
    """
    from repro.core.autotune import ep_capacity

    return ep_capacity(tokens_per_shard, cfg.top_k, cfg.num_experts,
                       cfg.capacity_factor)


def _resolve_exchange(cfg: ModelConfig, mux) -> tuple[str, str]:
    """ONE source of truth for the EP exchange policy: ``(impl, pack_impl)``.

    The ambient multiplexer (the serving engine's tuned policy object) wins
    when present — BOTH knobs come from it, so the pack layout and the
    transport can never disagree about whose policy is in force.  Without a
    mux, the legacy config knob drives the transport and the pack falls back
    to the one-hot reference.
    """
    if mux is not None:
        return mux.impl, mux.pack_impl
    return cfg.exchange_impl, "xla"


def _dispatch_slots(flat_dest: jax.Array, E: int, C: int, pack_impl: str):
    """slot(t, k) = expert * C + arrival rank; overflow -> the E*C drop bin.

    Two implementations of the same capacity-bounded packing (the paper's
    fixed-size reusable message pool), selected by the multiplexer's
    ``pack_impl`` knob exactly like the relational pack paths:

    * ``"xla"`` — one-hot/cumsum reference: materializes a ``[T, E]``
      running histogram in HBM;
    * ``"pallas"`` — :func:`repro.kernels.ops.moe_dispatch`: the arrival
      ranks come from per-block VMEM counters, nothing of shape ``[T, E]``
      exists (interpret mode off-TPU).

    Both produce bit-identical slots; returns ``(slot [T], kept [T])``.
    """
    if pack_impl == "pallas":
        from repro.kernels import ops

        slot, _ = ops.moe_dispatch(flat_dest, E, C)
        return slot, slot < E * C
    onehot = jax.nn.one_hot(flat_dest, E, dtype=jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - onehot
    my_rank = jnp.take_along_axis(rank, flat_dest[:, None], axis=1)[:, 0]
    kept = my_rank < C
    return jnp.where(kept, flat_dest * C + my_rank, E * C), kept


def _ep_moe_local(params, cfg: ModelConfig, x, axis_name: str,
                  pod_axis: str | None = None):
    """Per-shard body (inside shard_map, manual over the exchange axes).

    x: [T_loc, d] — this shard's slice of the token stream.  When an
    ambient :func:`repro.core.multiplexer.use_multiplexer` is active (the
    continuous serving engine's decode loop), the dispatch/return shuffles
    and the pack impl follow ITS tuned policy (:func:`_resolve_exchange`);
    otherwise the legacy ``cfg.exchange_impl`` transport with the XLA pack.

    On a pod mesh (``pod_axis`` set) a parallel unit is one device of the
    JOINT ``(pod, axis_name)`` axis and the dispatch/return trips take the
    two-level fabric — one coarse message per peer pod over the slow
    network, then the fine in-pod scheduled all-to-all — which is a pure
    permutation and therefore bit-identical to the flat route.

    ``cfg.moe_async_chunks > 1`` (or the ambient mux's ``pipeline_chunks``)
    splits the capacity dim into chunks and double-buffers: chunk ``c+1``'s
    dispatch is issued before chunk ``c``'s expert FFN, so XLA's async
    scheduler can overlap exchange DMA with expert compute (the same
    pipeline as the chunked relational shuffle).  Pure chunk-wise
    permutations on disjoint capacity slices — output is bit-identical for
    every chunk count dividing ``C``.
    """
    from repro.core.multiplexer import current_multiplexer

    mux = current_multiplexer()
    m = jax.lax.axis_size(axis_name)
    P_pods = jax.lax.axis_size(pod_axis) if pod_axis is not None else 1
    N = P_pods * m  # parallel units across BOTH network levels
    T_loc, d = x.shape
    E = cfg.num_experts
    E_loc = E // N
    assert params["w_gate"].shape[0] == E_loc, "expert weights must be pre-sharded"
    C = _ep_capacity(cfg, T_loc, N)
    dt = x.dtype
    impl, pack_impl = _resolve_exchange(cfg, mux)

    w, idx = route(params, cfg, x)  # [T_loc, k]

    # -- step 2: partition tuples into per-expert messages (the message pool).
    flat_dest = idx.reshape(-1)                       # [T_loc * k] expert ids
    flat_rows = jnp.repeat(x, cfg.top_k, axis=0)      # token copy per choice
    slot, kept = _dispatch_slots(flat_dest, E, C, pack_impl)
    buffers = jnp.zeros((E * C + 1, d), dt).at[slot].set(
        jnp.where(kept[:, None], flat_rows, 0)
    )[:-1]
    dropped = (~kept).sum()

    # -- step 3: the multiplexer shuffle to the experts' owner shards.
    # buffers [E, C, d] -> [N, E_loc * C_sub, d] by owner unit.
    if (pod_axis is None and mux is not None
            and mux.plan.pod_axis is not None and mux.plan.num_pods > 1):
        raise ValueError(
            "flat EP dispatch with a two-level multiplexer: the mesh has a "
            f"pod axis ({mux.plan.pod_axis!r}) but the MoE layer was not "
            "given it — a flat all-to-all here would silently route fine-"
            "grained traffic over the slow network.  Pass the pod axis "
            "through moe_ep (MeshContext.pod_axis) so dispatch/combine take "
            "the two-level fabric."
        )

    def ship_out(v):
        if pod_axis is not None:
            if mux is not None:
                return mux.dispatch(v, axis_name)
            return exchange.dispatch_two_level(v, axis_name, pod_axis, impl=impl)
        if mux is not None:
            return mux.all_to_all(v, axis_name)
        return exchange.all_to_all(v, axis_name, impl=impl)

    def ship_back(v):
        if pod_axis is not None:
            if mux is not None:
                return mux.combine(v, axis_name)
            return exchange.combine_two_level(v, axis_name, pod_axis, impl=impl)
        if mux is not None:
            return mux.all_to_all(v, axis_name)
        return exchange.all_to_all(v, axis_name, impl=impl)

    wg, wu, wd = (params[k].astype(dt) for k in ("w_gate", "w_up", "w_down"))

    chunks = mux.pipeline_chunks if mux is not None else cfg.moe_async_chunks
    if chunks < 1 or C % chunks:
        chunks = 1
    cc = C // chunks
    send = buffers.reshape(N, E_loc, C, d)

    # Double-buffered pipeline: chunk c+1's dispatch has no data dependence
    # on chunk c's expert FFN or return trip, so the async scheduler is free
    # to overlap exchange DMA with expert compute (paper §3.2: the
    # multiplexer ships message k while the workers fill k + 1).
    def dispatch_chunk(c: int):
        return ship_out(send[:, :, c * cc:(c + 1) * cc].reshape(N, E_loc * cc, d))

    inflight = dispatch_chunk(0)
    rets = []
    for c in range(chunks):
        got = inflight
        if c + 1 < chunks:
            inflight = dispatch_chunk(c + 1)
        # got[j] = slice from unit j destined to my local experts.
        recv = got.reshape(N, E_loc, cc, d).transpose(1, 0, 2, 3)
        recv = recv.reshape(E_loc, N * cc, d)
        # -- steps 5-6: process NUMA-local messages (batched expert FFN).
        # Expert weights arrive pre-sharded over the exchange axes
        # (in_specs) — the owner's slice is already local, zero weight
        # traffic.
        out = _expert_ffn(wg, wu, wd, recv)  # [E_loc, N*cc, d]
        # -- step 7: return trip through the same schedule.
        back = out.reshape(E_loc, N, cc, d).transpose(1, 0, 2, 3)
        rets.append(ship_back(back.reshape(N, E_loc * cc, d))
                    .reshape(N, E_loc, cc, d))

    ret = rets[0] if chunks == 1 else jnp.concatenate(rets, axis=2)
    ret = ret.reshape(E * C, d)
    ret = jnp.concatenate([ret, jnp.zeros((1, d), dt)])  # dropped bin reads 0

    # combine: out[t] = sum_k w[t,k] * ret[slot(t,k)]
    gathered = ret[slot].reshape(T_loc, cfg.top_k, d)
    y = jnp.einsum("tkd,tk->td", gathered, w.astype(dt))
    return y, dropped


def moe_ep(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Expert-parallel dispatch via shard_map over the exchange axes.

    On a single-level mesh the tokens/experts shard over ``exchange_axis``
    alone and dispatch is the flat scheduled all-to-all.  On a pod mesh
    (``ctx.pod_axis`` with size > 1) a parallel unit is one device of the
    joint ``(pod, exchange_axis)`` axis and dispatch/combine route through
    the two-level fabric — the flat route over a pod mesh is an explicit
    error (raised here and inside :func:`_ep_moe_local`), never a silent
    fine-grained shuffle over the slow network.
    """
    from repro.core.multiplexer import current_multiplexer

    ctx = current_mesh_context()
    assert ctx is not None, "ep_shardmap requires an active mesh context"
    axis = ctx.exchange_axis
    m = ctx.exchange_size
    pod = ctx.pod_axis
    pods = ctx.mesh.shape[pod] if pod is not None else 1
    if pods <= 1:
        pod = None
    N = (pods if pod is not None else 1) * m

    mux = current_multiplexer()
    if mux is not None and pod is not None and mux.plan.pod_axis is None:
        raise ValueError(
            "EP dispatch on a pod mesh with a single-level multiplexer: "
            f"the mesh context has pod axis {ctx.pod_axis!r} (size "
            f"{pods}) but the ambient mux's plan has none — its flat "
            "all-to-all would silently cross the slow network.  Build "
            "the multiplexer for the SAME two-level mesh "
            "(make_multiplexer(ctx.mesh, ...))."
        )

    T = x.shape[0]
    if N == 1 or T % N != 0 or T // N == 0 or cfg.num_experts % N != 0:
        return moe_dense(params, cfg, x)

    def body(params, x):
        y, _ = _ep_moe_local(params, cfg, x, axis, pod_axis=pod)
        return y

    # NOTE(§Perf C5/C6, refuted): pre-gathering bf16 expert weights to
    # axis-local replicas (with_sharding_constraint before the shard_map)
    # was tried to kill the ~288 GB/chip activation all-reduce that the
    # data-sharded weight contraction causes — GSPMD responded with
    # "involuntary full rematerialization" replicate-and-repartition around
    # the manual region, inflating compute 2.3-6x.  Keeping the storage
    # sharding; the structural fix is a fully-manual MoE block (all mesh
    # axes manual) or the Shardy partitioner — see EXPERIMENTS.md §Perf.
    ep_params = {k: params[k] for k in ("router", "w_gate", "w_up", "w_down")}
    # On a pod mesh the unit axis is the JOINT (pod, exchange) axis: tokens
    # and experts shard over both levels, and the manual region sees both
    # axis names so dispatch can run its two hops.
    unit = (pod, axis) if pod is not None else axis
    param_specs = {
        "router": P(None, None),          # small; replicated over the axis
        "w_gate": P(unit, None, None),    # experts stay sharded in place
        "w_up": P(unit, None, None),
        "w_down": P(unit, None, None),
    }
    from repro.compat import shard_map

    fn = shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(param_specs, P(unit, None)),
        out_specs=P(unit, None),
        axis_names={pod, axis} if pod is not None else {axis},
        check_vma=False,
    )
    return fn(ep_params, x)


# ----------------------------------------------------------------------------
# Layer entry point.
# ----------------------------------------------------------------------------

def moe_ffn(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """The FFN slot of a MoE transformer layer: routed + shared experts."""
    B, S, d = x.shape
    tokens = x.reshape(B * S, d)
    if cfg.moe_impl == "ep_shardmap":
        y = moe_ep(params, cfg, tokens)
    else:  # "dense" and "gspmd"
        y = moe_dense(params, cfg, tokens)
    y = y.reshape(B, S, d)
    if cfg.num_shared_experts:
        y = y + L.mlp_block(params["shared"], cfg, x)
    return y


__all__ = [
    "init_moe_layer",
    "specs_moe_layer",
    "route",
    "moe_dense",
    "moe_ep",
    "moe_ffn",
]
