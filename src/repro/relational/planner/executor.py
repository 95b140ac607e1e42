"""Plan executor: one shard_map over the local operators + the multiplexer.

Compiles a :class:`~repro.relational.planner.physical.PhysicalPlan` into a
single ``shard_map``-ed function: base tables enter as (columns, valid)
pytrees sharded over the query mesh, every ``Exchange`` edge is routed
through ONE per-query :class:`~repro.core.multiplexer.CommMultiplexer`
(knobs from the plan-time tuner, unless the caller pins them — the A/B
benchmarks and equivalence tests do), local operators come from
``relational/operators.py``, and the final combine is a psum (dense
group-bys, scalar aggregates) or a broadcast top-k merge.

The exchange contract is the repo-wide one: capacities are the static
zero-drop bound, the psum'd drop count of every exchange is summed and
checked after execution, and any overflow raises instead of silently
losing rows.

Two-level meshes (``num_pods > 1``): shuffles take
``hash_shuffle_global`` (coarse cross-pod hop + fine in-pod — DCI never
carries fine-grained traffic), broadcast edges obey the tuned
``cross_pod`` strategy (replicate, or hash-reshard by the build key), and
psum/top-k combines cross both axes.  Plans are mesh-shape-agnostic; only
this module touches devices.

Names on the device trace: the program is named after the plan
(``jit_q3``), and each physical node's ops run under a ``jax.named_scope``
of its kind (:data:`SCOPES`), so a device op's ``op_name`` says which
operator it belongs to.  Scopes are metadata: the compiled program is the
same with or without them.
"""

from __future__ import annotations

import math
import time
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...compat import fetch, make_mesh, shard_map
from ...core import exchange as core_exchange
from ...core.multiplexer import CommMultiplexer, make_multiplexer
from ...obs.trace import QueryTrace, maybe_span
from .. import operators as ops
from ..table import Table, pad_to, shard_rows
from .physical import PhysicalPlan, PNode

SHUFFLE_AXIS = "q"  # the in-pod (fast network) exchange axis

# The named scope of each physical node kind's ops; exchanges are scoped by
# ``exkind`` (``shuffle``/``broadcast``), and a join's payload gather runs
# in a ``gather_payload`` scope inside ``join_pk``.
SCOPES = {
    "scan": "scan",
    "filter": "filter",
    "project": "project",
    "join": "join_pk",
    "groupby_sorted": "groupby_sorted",
    "groupby_combine": "groupby_combine",
    "groupby_dense": "groupby_dense",
    "aggregate": "aggregate",
    "topk": "topk",
}


def _scope(n: PNode) -> str:
    return n.info["exkind"] if n.kind == "exchange" else SCOPES[n.kind]


def _mesh(num_shards: int, num_pods: int = 1):
    """Query mesh: 1-D single-pod, or two-level ``(pod, q)`` with the fine
    shuffle axis strictly in-pod."""
    if num_pods <= 1:
        return make_mesh((num_shards,), (SHUFFLE_AXIS,))
    if num_shards % num_pods:
        raise ValueError(
            f"num_shards={num_shards} does not split across "
            f"num_pods={num_pods}; pick a pod count dividing the shard count"
        )
    return make_mesh((num_pods, num_shards // num_pods), ("pod", SHUFFLE_AXIS))


def _axes(num_pods: int):
    """The mesh axes a table's rows are sharded over (shard_map specs and
    the final cross-unit psum both use this)."""
    return ("pod", SHUFFLE_AXIS) if num_pods > 1 else (SHUFFLE_AXIS,)


def _prep(table: Table, num_shards: int) -> Table:
    cap = math.ceil(table.capacity / num_shards) * num_shards
    return shard_rows(pad_to(table, cap), num_shards)


def _place(table: Table, num_shards: int, mesh, axes) -> tuple[dict, jax.Array]:
    """Prep a base table and put its ``(columns, valid)`` on the mesh once,
    row-sharded the way the shard_map reads them — a dispatch then moves no
    table data (on one device this is a no-op)."""
    t = _prep(table, num_shards)
    return jax.device_put((t.columns, t.valid), NamedSharding(mesh, P(axes)))


def resolve_knobs(
    tuned, impl: str, pack_impl: str | None, num_chunks: int | None
) -> dict:
    """The multiplexer knobs for a context's pins over a tuned config.

    ``impl="auto"`` applies the tuned knobs, with any explicitly passed
    knob pinned over the tuner's choice.  An explicit ``impl`` uses the
    caller's knobs verbatim with the pre-tuner defaults for anything unset.
    Returns ``make_multiplexer`` keyword arguments.
    """
    if impl == "auto":
        return dict(
            impl=tuned.impl,
            pack_impl=pack_impl or tuned.pack_impl,
            pipeline_chunks=num_chunks or tuned.pipeline_chunks,
            transport_chunks=tuned.transport_chunks,
        )
    return dict(impl=impl, pack_impl=pack_impl or "xla",
                pipeline_chunks=num_chunks or 1)


def _make_mux(
    mesh,
    plan: PhysicalPlan,
    impl: str,
    pack_impl: str | None,
    num_chunks: int | None,
) -> CommMultiplexer:
    """One multiplexer per query, from the PLAN-TIME tuned knobs (so
    ``explain()`` describes exactly what runs) and the caller's pins (see
    :func:`resolve_knobs`).  The ``cross_pod`` strategy is a plan shape
    (see ``plan_physical``), so the mux just records the plan's resolved
    choice for introspection.
    """
    return make_multiplexer(
        mesh, **resolve_knobs(plan.tuned, impl, pack_impl, num_chunks),
        cross_pod=plan.tuned.cross_pod or "broadcast",
    )


def _exchange_by_key(
    mux: CommMultiplexer, tbl: Table, key_name: str, columns: list[str],
    route_keys: jax.Array | None = None,
) -> tuple[Table, jax.Array]:
    """Decoupled exchange: repartition rows by hash(key) over the mesh.

    Routed through :meth:`CommMultiplexer.hash_shuffle_global`: the plain
    in-axis shuffle on single-level meshes, the coarse-cross-pod +
    fine-in-pod exchange on two-level ones.  Capacity per (src, dst)
    message equals the local capacity — the static zero-drop bound.
    ``route_keys`` overrides the ROUTING key only (the salted
    repartitioning: heavy rows route by ``key * num_salts + salt`` while
    the true key column ships unchanged in the row image).
    Returns ``(table, dropped)`` with ``dropped`` psum'd.
    """
    for c in columns:
        if not jnp.issubdtype(tbl[c].dtype, jnp.integer):
            raise TypeError(
                f"exchange of non-integer column {c!r} ({tbl[c].dtype}): "
                "the packed row image is int32 — keep float aggregates "
                "local (group after the exchange, not before)"
            )
    cap = tbl.valid.shape[0]
    rows = jnp.stack([tbl[c].astype(jnp.int32) for c in columns], axis=1)
    keys = tbl[key_name] if route_keys is None else route_keys
    out_rows, out_valid, dropped = mux.hash_shuffle_global(
        keys.astype(jnp.int32), rows, SHUFFLE_AXIS,
        capacity=cap, valid=tbl.valid,
    )
    cols = {c: out_rows[:, i] for i, c in enumerate(columns)}
    return Table(cols, out_valid), dropped


def _shuffle_histogram(
    keys: jax.Array, valid: jax.Array, num_shards: int, num_pods: int, axes
) -> tuple[jax.Array, jax.Array]:
    """Global arrival counts of a (routing-key, valid) pair.

    Uses the exact routing rule of the exchange (``fibonacci_hash % N``
    over the GLOBAL shard count — ``hash_shuffle`` single-level,
    ``hash_shuffle_two_level`` two-level, where destination ``d`` is in pod
    ``d // n`` at in-pod index ``d % n``, ``n = N / num_pods``), psum'd over
    the mesh in one collective.  Returns ``(counts, overload)``: ``counts``
    is ``int32[N + 2]``, the per-destination arrival histogram followed by
    the rows that cross pods (destination pod other than the sender's) and
    the rows the in-pod hop moves between chips (in-pod index other than
    the sender's: the cross-pod hop keeps it); ``overload = max_load /
    fair_share`` (1.0 = balanced).
    """
    n = num_shards // num_pods
    dest = (
        core_exchange.fibonacci_hash(keys.astype(jnp.int32))
        % jnp.uint32(num_shards)
    ).astype(jnp.int32)
    local = jnp.zeros((num_shards,), jnp.int32).at[dest].add(
        valid.astype(jnp.int32)
    )
    me = _global_shard_index(num_shards, num_pods)
    moved = jnp.stack([
        jnp.sum(valid & (dest // n != me // n), dtype=jnp.int32),
        jnp.sum(valid & (dest % n != me % n), dtype=jnp.int32),
    ])
    counts = lax.psum(jnp.concatenate([local, moved]), axes)
    hist = counts[:num_shards]
    total = jnp.maximum(hist.sum(), 1).astype(jnp.float32)
    overload = hist.max().astype(jnp.float32) * num_shards / total
    return counts, overload


def _global_shard_index(num_shards: int, num_pods: int) -> jax.Array:
    if num_pods > 1:
        return lax.axis_index("pod") * (num_shards // num_pods) + \
            lax.axis_index(SHUFFLE_AXIS)
    return lax.axis_index(SHUFFLE_AXIS)


def _route_and_report(
    tbl: Table, node: PNode, num_shards: int, num_pods: int, axes
) -> tuple[jax.Array | None, dict]:
    """Runtime re-optimization of one shuffle edge (paper §3.1).

    Every shuffle psums its per-shard destination histogram.  On an edge
    the planner marked salted, the MEASURED plain overload is compared to
    the plan's runtime threshold inside the jit: above it, heavy-key rows
    switch to the salted route (``key * num_salts + salt``, salt drawn
    per-row from the row index so one key spreads evenly); below it —
    stats were wrong, data is balanced — the exchange stays a plain hash
    and downstream partial+combine still reduces correctly.  Returns the
    routing-key override (None = plain) and the edge's report entry: the
    chosen route's ``counts`` (:func:`_shuffle_histogram`), both overloads
    and the salting decision, read back as the run's
    :class:`~repro.obs.trace.ExchangeEdge`.
    """
    info = node.info
    keys = tbl[info["key"]].astype(jnp.int32)
    counts_plain, over_plain = _shuffle_histogram(
        keys, tbl.valid, num_shards, num_pods, axes
    )
    if not info.get("salted"):
        return None, {
            "counts": counts_plain,
            "overload": over_plain,
            "plain_overload": over_plain,
            "salted": jnp.bool_(False),
        }
    s = int(info["num_salts"])
    heavy = jnp.asarray(info["heavy_keys"], jnp.int32)
    do_salt = over_plain > jnp.float32(info["runtime_threshold"])
    # Per-row salt: hash the global row position (decorrelated across
    # shards by the shard index) so each heavy key's rows spread evenly
    # over all its sub-keys regardless of their layout.
    gidx = _global_shard_index(num_shards, num_pods).astype(jnp.uint32)
    iota = jnp.arange(keys.shape[0], dtype=jnp.uint32)
    rsalt = (
        core_exchange.fibonacci_hash(
            iota + gidx * jnp.uint32(0x9E3779B9)
        ) % jnp.uint32(s)
    ).astype(jnp.int32)
    salted_keys = keys * jnp.int32(s) + rsalt
    route = jnp.where(
        do_salt & jnp.isin(keys, heavy) & tbl.valid, salted_keys, keys
    )
    counts, overload = _shuffle_histogram(
        route, tbl.valid, num_shards, num_pods, axes
    )
    return route, {
        "counts": counts,
        "overload": overload,
        "plain_overload": over_plain,
        "salted": do_salt,
    }


def _broadcast_table(
    mux: CommMultiplexer, tbl: Table, columns: list[str]
) -> tuple[Table, jax.Array]:
    """Deliver a join's (small) build side to where the probe rows are.

    Single-level mesh: ring all-gather.  Two-level mesh: in-pod all-gather,
    then one coarse cross-pod all-gather — the build side crosses DCI once
    per remote pod.  (The alternative ``cross_pod="reshard"`` strategy is a
    *plan shape*, not a transport swap: the planner rebuilds the join as
    co-partitioned, because resharding only the build side would strand it
    away from an un-partitioned probe.)
    """
    cols = {}
    for c in columns:
        cols[c] = mux.broadcast_global(tbl[c], SHUFFLE_AXIS).reshape(-1)
    v = mux.broadcast_global(tbl.valid, SHUFFLE_AXIS).reshape(-1)
    return Table(cols, v), jnp.int32(0)


def _report_keys(root: PNode) -> dict[int, str]:
    """Stable per-edge keys for ``run.exchange_report``.

    The display index (``PNode.idx``) renumbers whenever an unrelated part
    of the plan changes shape — salting an edge inserts combine nodes,
    reshard rebuilds a join — so a report keyed on it is NOT comparable
    across plan variants, cached reloads, or replans of the same query.
    Reports instead key on the shuffle's first-visit ordinal plus its key
    column (``shuffle[l_partkey]#0``): a pure function of the shuffle edges
    themselves, identical for cold, warm, and unpickled plans.
    """
    seen: set[int] = set()
    order: list[PNode] = []

    def walk(n: PNode):
        if id(n) in seen:
            return
        seen.add(id(n))
        if n.kind == "exchange" and n.info["exkind"] == "shuffle":
            order.append(n)
        for c in n.children:
            walk(c)

    walk(root)
    return {
        id(n): f"shuffle[{n.info['key']}]#{j}" for j, n in enumerate(order)
    }


def _raise_on_dropped(query: str, dropped) -> None:
    """Capacity overflow is an error, not silent row loss (paper: the message
    pool is sized so overflow cannot happen; if it does, results are wrong)."""
    d = int(fetch(dropped))
    if d:
        raise RuntimeError(
            f"{query}: exchange dropped {d} rows to capacity overflow — "
            "results would silently lose rows; raise the capacity bound"
        )


def _check_vma(plan: PhysicalPlan, mux: CommMultiplexer) -> bool:
    """Keep the replication checker on only where it has rules: the top-k
    broadcast combine, pallas_call packs, and two-level ppermute hierarchies
    all lack VMA rules (same conditions the hand-written plans used)."""
    return (
        plan.root.kind != "topk"
        and mux.pack_impl != "pallas"
        and plan.num_pods == 1
    )


def _resolve_exec_ctx(plan: PhysicalPlan, ctx, where: str):
    """Resolve the context for this plan.

    The bare two-argument call (``compile_plan(plan, tables)``) is
    first-class API — it resolves to the plan's own mesh shape with default
    knobs.  Anything else must be an :class:`ExecutionContext` whose mesh
    shape matches the plan's (the PR-9 per-knob kwarg shim is gone; old
    spellings raise ``TypeError``).
    """
    from ..context import ExecutionContext, require_context

    if ctx is None:
        ctx = ExecutionContext(plan.num_shards, num_pods=plan.num_pods)
    ctx = require_context(ctx, where=where)
    if (ctx.num_shards, ctx.num_pods) != (plan.num_shards, plan.num_pods):
        raise ValueError(
            f"{where}: context mesh {ctx.num_shards}x{ctx.num_pods} does not "
            f"match the plan's {plan.num_shards}x{plan.num_pods}; re-plan or "
            "fix the context"
        )
    return ctx


def _resident_table(name: str, obj) -> Table:
    """Coerce a Table-or-DataSource to an in-memory Table (the executor's
    unit of work); chunked sources belong to the streamed path."""
    if isinstance(obj, Table):
        return obj
    from ..source import DataSource

    if isinstance(obj, DataSource):
        if obj.is_chunked:
            raise ValueError(
                f"table {name!r} is a chunked DataSource; in-memory "
                "execution cannot hold it — run through run_query (or "
                "stream.compile_plan_streamed) for out-of-core execution"
            )
        return obj.materialize()
    raise TypeError(f"table {name!r}: expected Table or DataSource, got {type(obj)!r}")


def _check_row_budget(plan: PhysicalPlan, tables: dict[str, Table], ctx) -> None:
    """``device_row_budget`` is a hard promise: in-memory execution refuses
    base tables whose per-shard slice exceeds it (chunk them instead)."""
    if ctx.device_row_budget is None:
        return
    for name in plan.scans:
        per_shard = math.ceil(tables[name].capacity / plan.num_shards)
        if per_shard > ctx.device_row_budget:
            raise ValueError(
                f"table {name!r} needs {per_shard} rows/device, over "
                f"device_row_budget={ctx.device_row_budget}; stream it as a "
                "chunked DataSource (run_query with morsel_rows) instead"
            )


def execute_plan(plan: PhysicalPlan, tables: dict, ctx=None):
    """Run a physical plan over real data; returns the fetched result dict.

    ``tables`` maps base-table names to :class:`Table`\\ s (or
    :class:`~repro.relational.source.DataSource`\\ s) whose capacities match
    the catalog the plan was built from.  A chunked source switches to
    morsel-streamed out-of-core execution
    (:func:`~repro.relational.planner.stream.compile_plan_streamed`);
    everything resident runs the one-shard_map in-memory path.  ``ctx`` is
    an :class:`~repro.relational.context.ExecutionContext` (or None for the
    plan's own mesh with default knobs).
    """
    ctx = _resolve_exec_ctx(plan, ctx, where="execute_plan")
    from ..source import DataSource

    if any(
        isinstance(t, DataSource) and t.is_chunked for t in tables.values()
    ):
        from .stream import compile_plan_streamed

        return compile_plan_streamed(plan, tables, ctx)()
    return compile_plan(plan, tables, ctx)()


def compile_plan(
    plan: PhysicalPlan,
    tables: dict,
    ctx=None,
    mux: CommMultiplexer | None = None,
):
    """Build a zero-arg runner for the plan (jit object created once, so
    repeated calls hit the compile cache — what the benchmarks time).

    ``ctx`` is an :class:`~repro.relational.context.ExecutionContext`
    carrying the multiplexer knobs (its mesh shape must match the plan's);
    omitted, the plan's own mesh with default knobs applies.

    ``mux`` injects a SHARED multiplexer instead of building the per-query
    one: the query-serving engine tunes one knob set over every concurrent
    plan's exchanges (:func:`repro.core.autotune.tune_shared_config`) and
    passes it here, so compatible plans running together ride the same
    tuned schedules.  The mux must have been built for this plan's mesh
    shape; its knobs override the plan-time tuner's.

    The returned :class:`CompiledRunner` is callable (run to completion) or
    split-phase: ``run.dispatch()`` launches without a host sync and
    ``run.finalize(out)`` / ``run.collect(out)`` fetch+check — the serving
    engine dispatches a whole admission round before finalizing any of it,
    so concurrent queries overlap on the XLA async runtime.  ``collect``
    additionally returns the run's :class:`~repro.obs.trace.QueryTrace`
    (per-edge measured bytes, destination histograms, salting decisions,
    model predictions) without mutating the runner — the runner is shared
    across concurrent callers, so per-run telemetry never lives on it.
    """
    ctx = _resolve_exec_ctx(plan, ctx, where="compile_plan")
    impl, pack_impl, num_chunks = ctx.impl, ctx.pack_impl, ctx.num_chunks
    num_shards, num_pods = plan.num_shards, plan.num_pods
    tables = {name: _resident_table(name, tables[name]) for name in plan.scans}
    _check_row_budget(plan, tables, ctx)
    for name in plan.scans:
        if tables[name].capacity != plan.catalog[name]:
            raise ValueError(
                f"table {name!r} has capacity {tables[name].capacity} but the "
                f"plan was built for {plan.catalog[name]}; re-plan for the "
                "actual tables"
            )
    mesh = _mesh(num_shards, num_pods)
    axes = _axes(num_pods)
    if mux is None:
        # the runner itself stays tracer-free: it may be memoized and
        # shared with untraced contexts
        with maybe_span(ctx.trace, "repro.mux", "compile",
                        query=plan.name) as s:
            mux = _make_mux(mesh, plan, impl, pack_impl, num_chunks)
            if s is not None:
                s.args.update(mux.describe())
    single = num_shards == 1 and num_pods == 1
    report_keys = _report_keys(plan.root)

    def body(*flat):
        tabs = {
            name: Table(dict(flat[2 * i]), flat[2 * i + 1])
            for i, name in enumerate(plan.scans)
        }
        drops: list[jax.Array] = []
        reports: dict[str, dict] = {}
        memo: dict[int, object] = {}

        def ev(n: PNode):
            if id(n) in memo:
                return memo[id(n)]
            for c in n.children:  # outside this node's scope
                ev(c)
            with jax.named_scope(_scope(n)):
                r = _eval(n)
            memo[id(n)] = r
            return r

        def _agg_dict(t: Table, aggs):
            return {
                name: (e.eval(t), kind) for name, e, kind in aggs
            }

        def _eval(n: PNode):
            if n.kind == "scan":
                src = tabs[n.info["table"]]
                return Table({c: src[c] for c in n.schema}, src.valid)
            if n.kind == "filter":
                t = ev(n.children[0])
                return t.with_mask(n.info["pred"].eval(t))
            if n.kind == "project":
                t = ev(n.children[0])
                cols = {c: t[c] for c in n.info["keep"]}
                for name, e in n.info["derived"]:
                    cols[name] = e.eval(t)
                return Table(cols, t.valid)
            if n.kind == "exchange":
                t = ev(n.children[0])
                if single:  # hash % 1 == 0: the exchange is the identity
                    return t
                if n.info["exkind"] == "shuffle":
                    route, rep = _route_and_report(
                        t, n, num_shards, num_pods, axes
                    )
                    out, d = _exchange_by_key(
                        mux, t, n.info["key"], list(n.schema),
                        route_keys=route,
                    )
                    reports[report_keys[id(n)]] = rep
                else:
                    out, d = _broadcast_table(mux, t, list(n.schema))
                drops.append(d)
                return out
            if n.kind == "join":
                b, p = ev(n.children[0]), ev(n.children[1])
                bidx, match = ops.join_pk(
                    b[n.info["build_key"]], b.valid,
                    p[n.info["probe_key"]], p.valid,
                )
                cols = dict(p.columns)
                with jax.named_scope("gather_payload"):
                    cols.update(ops.gather_payload(
                        b, bidx, match, list(n.info["payload"])
                    ))
                return Table(cols, match)
            if n.kind == "groupby_sorted":
                t = ev(n.children[0])
                gkeys, gvalid, out = ops.groupby_sorted(
                    t[n.info["key"]], t.valid, _agg_dict(t, n.info["aggs"])
                )
                return Table({n.info["key"]: gkeys, **out}, gvalid)
            if n.kind == "groupby_combine":
                # merge salted partials: every shard holds ALL partial
                # groups (they arrive by broadcast), so re-grouping by the
                # true key and re-summing the partial sums/counts — counts
                # are small exact integers in f32 — yields the exact global
                # aggregate, replicated.
                t = ev(n.children[0])
                aggs = {
                    name: (t[name], "sum") for name, _e, _k in n.info["aggs"]
                }
                gkeys, gvalid, out = ops.groupby_sorted(
                    t[n.info["key"]], t.valid, aggs
                )
                return Table({n.info["key"]: gkeys, **out}, gvalid)
            if n.kind == "groupby_dense":
                t = ev(n.children[0])
                res = ops.groupby_dense(
                    n.info["key_expr"].eval(t),
                    n.info["num_groups"],
                    _agg_dict(t, n.info["aggs"]),
                    t.valid,
                )
                return jax.tree.map(lambda x: lax.psum(x, axes), res)
            if n.kind == "aggregate":
                t = ev(n.children[0])
                out = {}
                for name, e, kind in n.info["aggs"]:
                    local = (
                        ops.sum_where(e.eval(t), t.valid)
                        if kind == "sum"
                        else ops.count_where(t.valid)
                    )
                    out[name] = lax.psum(local, axes)
                return out
            if n.kind == "topk":
                t = ev(n.children[0])
                k = n.info["k"]
                vals, payload = ops.topk_rows(
                    t[n.info["key"]], t.valid, k,
                    {c: t[c] for c in n.info["payload"]},
                )
                # topk_rows pads to k with -inf sort keys; surface validity
                # so fewer-than-k matches don't leak garbage rows
                if single:
                    return {**payload, "_valid": ~jnp.isneginf(vals)}
                all_vals = mux.broadcast_global(vals, SHUFFLE_AXIS).reshape(-1)
                gathered = {
                    c: mux.broadcast_global(col, SHUFFLE_AXIS).reshape(-1)
                    for c, col in payload.items()
                }
                top_vals, idx = lax.top_k(all_vals, k)
                out = {c: col[idx] for c, col in gathered.items()}
                out["_valid"] = ~jnp.isneginf(top_vals)
                return out
            raise TypeError(f"unknown physical node kind {n.kind!r}")

        result = ev(plan.root)
        dropped = sum(drops) if drops else jnp.int32(0)
        return result, dropped, reports

    flat = []
    for name in plan.scans:
        flat.extend(_place(tables[name], num_shards, mesh, axes))
    body.__name__ = body.__qualname__ = plan.name  # the program: jit_<plan>
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axes),) * len(flat),
        out_specs=(P(), P(), P()),
        check_vma=_check_vma(plan, mux),
    )
    jfn = jax.jit(fn)
    from ...obs import model_check as _mc

    models = _mc.edge_models(plan)
    return CompiledRunner(plan, jfn, flat, models)


class RunnerBase:
    """Shared surface of the in-memory and streamed runners.

    Per-run telemetry travels through :meth:`collect`'s return value, not
    the runner: compiled runners are memoized and shared across concurrent
    callers, so a mutable report attribute is a data race (two overlapped
    ``finalize`` calls clobber each other's reports).  The deprecated
    ``exchange_report`` property remains as a warned view of the LAST
    finalized run for single-caller code; concurrent callers must use
    ``collect``.
    """

    _last_trace: QueryTrace | None = None

    @property
    def last_trace(self) -> QueryTrace | None:
        """The :class:`QueryTrace` of the most recent finalized run (None
        before the first)."""
        return self._last_trace

    @property
    def exchange_report(self) -> dict:
        """Deprecated last-run report view; racy under concurrency."""
        warnings.warn(
            "run.exchange_report is deprecated: it reflects only the LAST "
            "finalized run, which races under concurrent serving. Use "
            "result, trace = run.collect(run.dispatch()) and "
            "trace.exchange_report() (or trace.edges) instead.",
            DeprecationWarning,
            stacklevel=2,
        )
        qt = self._last_trace
        return qt.exchange_report() if qt is not None else {}


class CompiledRunner(RunnerBase):
    """Zero-arg in-memory runner with split-phase dispatch/collect."""

    def __init__(self, plan: PhysicalPlan, jfn, flat, models: dict):
        self._plan = plan
        self._jfn = jfn
        self._flat = flat
        self._models = models

    def dispatch(self):
        """Launch the jitted program without waiting on the host — results
        are live device values (XLA async dispatch)."""
        return self._jfn(*self._flat)

    def collect(self, out, t_dispatch: float | None = None):
        """Fetch + check a ``dispatch()`` result; returns ``(result,
        QueryTrace)`` without touching runner state (safe under
        concurrency).  ``t_dispatch`` (a ``time.perf_counter()`` reading
        taken just before ``dispatch``) prices the trace's measured wall.

        Two spans: ``repro.wait``, the host waiting for the program, then
        ``repro.fetch``, the drop check and the device-to-host reads (one
        ``repro.transfer`` per array, in :func:`~repro.compat.fetch`).
        Inside a traced span they are kept by its tracer too.
        """
        from ...obs.model_check import build_query_trace

        with maybe_span(None, "repro.wait", "execute"):
            jax.block_until_ready(out)
        with maybe_span(None, "repro.fetch", "execute"):
            result, dropped, reports = out
            _raise_on_dropped(self._plan.name, dropped)
            fetched = fetch(result)
            measured = (
                time.perf_counter() - t_dispatch
                if t_dispatch is not None else None
            )
            qt = build_query_trace(
                self._plan, fetch(reports), self._models, measured_s=measured
            )
        return fetched, qt

    def finalize(self, out, t_dispatch: float | None = None):
        """``collect`` plus last-trace bookkeeping; returns the result."""
        result, qt = self.collect(out, t_dispatch)
        self._last_trace = qt
        return result

    def __call__(self):
        t0 = time.perf_counter()
        return self.finalize(self.dispatch(), t_dispatch=t0)


__all__ = [
    "execute_plan",
    "compile_plan",
    "RunnerBase",
    "CompiledRunner",
    "_exchange_by_key",
    "_broadcast_table",
    "_raise_on_dropped",
    "_report_keys",
    "_mesh",
    "_axes",
    "_prep",
    "_place",
    "_make_mux",
]
