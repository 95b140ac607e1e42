"""Out-of-core morsel-streamed plan execution.

The in-memory executor (:mod:`.executor`) evaluates the whole plan in one
shard_map over full-capacity tables, so scale factor is bounded by device
memory.  This module executes the SAME physical plan chunk-at-a-time: one
base table is a chunked :class:`~repro.relational.source.DataSource` whose
fixed-capacity morsels stream through the pipeline with double-buffered
host→device prefetch (:class:`~repro.data.pipeline.Prefetcher`), while
every pipeline *breaker* (aggregates, group-bys, top-k) keeps a
fixed-shape per-shard partial state that each morsel merges into — the
``GroupByCombine`` semantics (re-group partials by the true key, re-sum
sums AND counts) applied incrementally.

Execution is decomposed into **passes**: breakers whose inputs contain no
other breaker run in pass 1, breakers over pass-1 outputs run in pass 2,
and so on (Q17 is the canonical two-pass query: pass 1 builds the per-part
average over the morsel stream, pass 2 re-scans the stream and aggregates
against it).  A pass whose breakers never touch the streamed scan runs as
a single step over resident inputs; the others loop over the morsels.
Non-breaker work upstream of a breaker (filters, projects, joins, the
build-side broadcast) re-evaluates per morsel — compute is traded for
memory, which is the out-of-core deal.

Exchanges inside the streamed pipeline move one morsel at a time, sized
for structural zero drop by default.  A tighter per-(src,dst) message
capacity (``ExecutionContext.exchange_rows``) can overflow; with
``spill=True`` overflow rows are withheld on the sender
(:func:`repro.core.exchange.hash_shuffle_spill`), parked in a host-memory
overflow partition, and re-offered in drain rounds after the morsel loop —
rows are never silently lost, and with spill disabled overflow raises
exactly like the in-memory executor's drop check.

Not supported streamed (raises ``NotImplementedError``): salted/adaptive
plans (``groupby_combine``), joins whose BUILD side streams, and non-
group-by breaker outputs consumed by later passes.  Plans built with
``StatsMode.STATIC`` never contain the former.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...compat import fetch, shard_map
from ...data.pipeline import Prefetcher
from ...obs.trace import deposit, maybe_span
from .. import operators as ops
from ..source import DataSource, as_source
from ..table import Table, from_numpy, pad_to
from .executor import (
    SHUFFLE_AXIS,
    RunnerBase,
    _axes,
    _make_mux,
    _mesh,
    _prep,
    _raise_on_dropped,
    _report_keys,
    _shuffle_histogram,
)
from .physical import PhysicalPlan, PNode

BREAKER_KINDS = frozenset(
    {"groupby_sorted", "groupby_combine", "groupby_dense", "aggregate", "topk"}
)

# Drain rounds make monotonic progress (every round delivers at least one
# row per backlogged destination), so this bound only trips on a logic bug.
MAX_DRAIN_ROUNDS = 1000


def _walk_unique(root: PNode):
    seen: set[int] = set()

    def go(n: PNode):
        if id(n) in seen:
            return
        seen.add(id(n))
        yield n
        for c in n.children:
            yield from go(c)

    yield from go(root)


class _StreamedPlan:
    """Static analysis of one physical plan against one streamed scan:
    which nodes vary morsel-to-morsel, and which pass each breaker runs in."""

    def __init__(self, plan: PhysicalPlan, streamed_table: str):
        self.plan = plan
        self.streamed_table = streamed_table
        self._streamed: dict[int, bool] = {}
        for n in _walk_unique(plan.root):
            if n.kind == "groupby_combine":
                raise NotImplementedError(
                    "salted/adaptive plans cannot stream; plan with "
                    "StatsMode.STATIC for out-of-core execution"
                )
            if (
                n.kind == "exchange"
                and isinstance(n.part, tuple)
                and n.part[0] == "salted"
            ):
                raise NotImplementedError("salted exchanges cannot stream")
        if plan.root.kind not in BREAKER_KINDS:
            raise ValueError("plan root must be an aggregation/top-k to stream")
        self.breakers = [
            n for n in _walk_unique(plan.root) if n.kind in BREAKER_KINDS
        ]
        self.pass_of: dict[int, int] = {}
        for b in self.breakers:
            self._assign_pass(b)
        self.num_passes = max(self.pass_of.values(), default=1)

    def streamed(self, n: PNode) -> bool:
        """Does this node's output change morsel to morsel?"""
        if id(n) in self._streamed:
            return self._streamed[id(n)]
        if n.kind == "scan":
            r = n.info["table"] == self.streamed_table
        elif n.kind in BREAKER_KINDS:
            r = False  # breaker output is resident state
        elif n.kind == "join":
            build, probe = n.children
            if self.streamed(build):
                raise NotImplementedError(
                    "join build side streams: streamed execution requires "
                    "the chunked table on the probe side"
                )
            r = self.streamed(probe)
        else:
            r = any(self.streamed(c) for c in n.children)
        self._streamed[id(n)] = r
        return r

    def _upstream_breakers(self, n: PNode) -> list[PNode]:
        out: list[PNode] = []
        seen: set[int] = set()

        def go(m: PNode):
            for c in m.children:
                if id(c) in seen:
                    continue
                seen.add(id(c))
                if c.kind in BREAKER_KINDS:
                    out.append(c)
                else:
                    go(c)

        go(n)
        return out

    def _assign_pass(self, b: PNode) -> int:
        if id(b) in self.pass_of:
            return self.pass_of[id(b)]
        ups = self._upstream_breakers(b)
        p = 1 + max((self._assign_pass(u) for u in ups), default=0)
        self.pass_of[id(b)] = p
        return p

    def pass_breakers(self, p: int) -> list[PNode]:
        return [b for b in self.breakers if self.pass_of[id(b)] == p]

    def shuffles_feeding(self, b: PNode, streamed_only: bool) -> list[PNode]:
        """Shuffle exchanges on ``b``'s input side, not crossing breakers."""
        out: list[PNode] = []
        seen: set[int] = set()

        def go(m: PNode):
            if id(m) in seen or m.kind in BREAKER_KINDS:
                return
            seen.add(id(m))
            if m.kind == "exchange" and m.info["exkind"] == "shuffle":
                if not streamed_only or self.streamed(m):
                    out.append(m)
            for c in m.children:
                go(c)

        go(b.children[0])
        return out


def _bname(n: PNode) -> str:
    return f"b{n.idx}"


def compile_plan_streamed(
    plan: PhysicalPlan,
    sources: dict[str, DataSource | Table],
    ctx,
    mux=None,
):
    """Build a zero-arg runner that streams the plan over morsels.

    ``sources`` maps every base table of the plan to a Table or DataSource;
    exactly one must be chunked (``num_chunks > 1``) — that relation
    streams, everything else stays resident.  ``ctx`` is an
    :class:`~repro.relational.context.ExecutionContext` (morsel/spill knobs
    plus the usual multiplexer knobs).  The runner returns the same result
    shape as the in-memory executor (integer outputs bit-identical; float
    aggregates differ only by f32 summation order) and exposes ``.stats``
    with morsel/pass/spill/prefetch-overlap counters.
    """
    num_shards, num_pods = plan.num_shards, plan.num_pods
    srcs = {name: as_source(sources[name]) for name in plan.scans}
    for name in plan.scans:
        if srcs[name].capacity != plan.catalog[name]:
            raise ValueError(
                f"source {name!r} has capacity {srcs[name].capacity} but the "
                f"plan was built for {plan.catalog[name]}; re-plan for the "
                "actual sources"
            )
    chunked = [n for n in plan.scans if srcs[n].is_chunked]
    if len(chunked) != 1:
        raise ValueError(
            f"streamed execution needs exactly one chunked source, got "
            f"{chunked or 'none'}; use execute_plan for fully in-memory runs"
        )
    streamed_name = chunked[0]
    sp = _StreamedPlan(plan, streamed_name)
    src = srcs[streamed_name]

    mesh = _mesh(num_shards, num_pods)
    axes = _axes(num_pods)
    report_keys = _report_keys(plan.root)
    tracer = ctx.trace
    if mux is None:
        mux = _make_mux(mesh, plan, ctx.impl, ctx.pack_impl, ctx.num_chunks)
    if ctx.spill and mux.plan.pod_axis is not None:
        raise NotImplementedError(
            "spill is single-level only; on pod meshes stream with "
            "zero-drop exchange capacity (exchange_rows=None)"
        )
    single = num_shards == 1 and num_pods == 1

    # Per-shard row capacity of one prepped morsel — every streamed
    # pipeline node keeps this capacity (filters/projects/joins preserve it).
    morsel_cap = math.ceil(src.chunk_rows / num_shards) * num_shards
    per_shard = morsel_cap // num_shards

    budget = ctx.device_row_budget
    if budget is not None:
        if per_shard > budget:
            raise ValueError(
                f"morsel slice of {per_shard} rows/device exceeds "
                f"device_row_budget={budget}; use smaller chunks"
            )
        for name in plan.scans:
            if name == streamed_name:
                continue
            resident_ps = math.ceil(srcs[name].capacity / num_shards)
            if resident_ps > budget:
                raise ValueError(
                    f"resident table {name!r} needs {resident_ps} rows/device,"
                    f" over device_row_budget={budget}; chunk it or raise the "
                    "budget"
                )

    resident_names = [n for n in plan.scans if n != streamed_name]
    resident_prepped = [
        _prep(srcs[name].materialize(), num_shards) for name in resident_names
    ]

    # The pass schedule: streamed breakers join the morsel loop, resident
    # ones run a single step (their input never touches the morsel — one
    # step per pass, or they would multiply-count).
    pass_plan = []
    for p in range(1, sp.num_passes + 1):
        bs = sp.pass_breakers(p)
        streamed_bs = [b for b in bs if sp.streamed(b.children[0])]
        resident_bs = [b for b in bs if not sp.streamed(b.children[0])]
        spill_nodes: list[PNode] = []
        if ctx.spill:
            seen: set[int] = set()
            for b in streamed_bs:
                for x in sp.shuffles_feeding(b, streamed_only=True):
                    if id(x) not in seen:
                        seen.add(id(x))
                        spill_nodes.append(x)
            if len(spill_nodes) > 1:
                raise NotImplementedError(
                    "spill supports one streamed shuffle per pass"
                )
        pass_plan.append((p, streamed_bs, resident_bs, spill_nodes))

    # ---- breaker state templates (global shapes, leading dim = num_shards)
    def _group_cap(n: PNode) -> int:
        if ctx.group_state_rows is not None:
            return int(ctx.group_state_rows)
        cap = n.cap
        if budget is not None:
            cap = min(cap, budget)
        return max(int(cap), 1)

    def _init_state(n: PNode):
        N = num_shards
        if n.kind == "aggregate":
            return {
                name: jnp.zeros((N,), jnp.float32 if kind == "sum" else jnp.int32)
                for name, _e, kind in n.info["aggs"]
            }
        if n.kind == "groupby_dense":
            G = n.info["num_groups"]
            return {
                name: jnp.zeros((N, G), jnp.float32 if kind == "sum" else jnp.int32)
                for name, _e, kind in n.info["aggs"]
            }
        if n.kind == "groupby_sorted":
            C = _group_cap(n)
            return {
                "keys": jnp.zeros((N, C), jnp.int32),
                "valid": jnp.zeros((N, C), jnp.bool_),
                "aggs": {
                    name: jnp.zeros((N, C), jnp.float32)
                    for name, _e, _k in n.info["aggs"]
                },
                "overflow": jnp.zeros((N,), jnp.int32),
            }
        if n.kind == "topk":
            child = n.children[0]
            k = n.info["k"]
            return {
                "vals": jnp.full((N, k), -jnp.inf, jnp.float32),
                "payload": {
                    c: jnp.zeros(
                        (N, k),
                        jnp.float32 if c in child.float_cols else jnp.int32,
                    )
                    for c in n.info["payload"]
                },
            }
        raise NotImplementedError(f"no streamed state for breaker {n.kind!r}")

    states = {_bname(b): _init_state(b) for b in sp.breakers}
    if budget is not None:
        for b in sp.breakers:
            if b.kind == "groupby_sorted" and _group_cap(b) > budget:
                raise ValueError(
                    f"group state of {_group_cap(b)} rows/device exceeds "
                    f"device_row_budget={budget}; set group_state_rows"
                )

    # ---- per-step evaluation ---------------------------------------------
    def _exchange_streamed(t: Table, n: PNode, spills, reports,
                           do_spill: bool, bounded: bool):
        """One morsel's worth of rows through the decoupled exchange.

        ``bounded``: apply ``ctx.exchange_rows`` as the per-(src,dst)
        message capacity (streamed shuffles and drain re-offers only;
        resident exchanges keep the zero-drop bound).  The per-destination
        arrival histogram is psum'd into ``reports`` ALWAYS (same
        always-on discipline as the in-memory executor) — tracing decides
        who reads it, never whether it exists, so the jitted program is
        identical traced and untraced."""
        columns = list(n.schema)
        cap = t.valid.shape[0]
        msg_cap = cap
        if bounded and ctx.exchange_rows is not None:
            msg_cap = min(cap, int(ctx.exchange_rows))
        rows = jnp.stack([t[c].astype(jnp.int32) for c in columns], axis=1)
        keys = t[n.info["key"]].astype(jnp.int32)
        counts, _over = _shuffle_histogram(
            keys, t.valid, num_shards, num_pods, axes
        )
        reports[report_keys[id(n)]] = counts
        if do_spill:
            out_rows, out_valid, spilled = mux.hash_shuffle_spill(
                keys, rows, SHUFFLE_AXIS, capacity=msg_cap, valid=t.valid
            )
            spills[id(n)] = (rows, spilled)
            dropped = jnp.int32(0)
        else:
            out_rows, out_valid, dropped = mux.hash_shuffle_global(
                keys, rows, SHUFFLE_AXIS, capacity=msg_cap, valid=t.valid
            )
        cols = {c: out_rows[:, i] for i, c in enumerate(columns)}
        return Table(cols, out_valid), dropped

    def _make_ev(tabs, local_states, drops, spills, spill_ids, reports,
                 drain_for=None):
        """Node evaluator for one step.

        ``tabs``: base-table name -> per-shard Table (the streamed scan's
        entry is the current morsel, or None in drain/resident-only steps).
        ``spill_ids``: exchange node ids that run the spill-capable path.
        ``drain_for``: (exchange_node_id, drain_table) — overrides that
        exchange to re-offer spilled rows instead of evaluating its child.
        """
        memo: dict[int, object] = {}

        def ev(n: PNode):
            if id(n) in memo:
                return memo[id(n)]
            r = _eval(n)
            memo[id(n)] = r
            return r

        def _agg_dict(t: Table, aggs):
            return {name: (e.eval(t), kind) for name, e, kind in aggs}

        def _eval(n: PNode):
            if n.kind in BREAKER_KINDS:
                # consumed output of an earlier pass: rebuild from state
                if n.kind != "groupby_sorted":
                    raise NotImplementedError(
                        f"streamed consumption of {n.kind} output"
                    )
                st = local_states[_bname(n)]
                cols = {n.info["key"]: st["keys"][0]}
                for name, _e, _k in n.info["aggs"]:
                    cols[name] = st["aggs"][name][0]
                return Table(cols, st["valid"][0])
            if n.kind == "scan":
                src_t = tabs[n.info["table"]]
                if src_t is None:
                    raise NotImplementedError(
                        "drain pass reached the streamed scan off the "
                        "spilling exchange's path"
                    )
                return Table({c: src_t[c] for c in n.schema}, src_t.valid)
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
                if drain_for is not None and id(n) == drain_for[0]:
                    t = drain_for[1]
                else:
                    t = ev(n.children[0])
                if single:
                    return t
                if n.info["exkind"] == "shuffle":
                    out, d = _exchange_streamed(
                        t, n, spills, reports,
                        do_spill=id(n) in spill_ids,
                        bounded=sp.streamed(n)
                        or (drain_for is not None and id(n) == drain_for[0]),
                    )
                else:
                    cols = {
                        c: mux.broadcast_global(t[c], SHUFFLE_AXIS).reshape(-1)
                        for c in n.schema
                    }
                    v = mux.broadcast_global(t.valid, SHUFFLE_AXIS).reshape(-1)
                    out, d = Table(cols, v), jnp.int32(0)
                drops.append(d)
                return out
            if n.kind == "join":
                b, p = ev(n.children[0]), ev(n.children[1])
                bidx, match = ops.join_pk(
                    b[n.info["build_key"]], b.valid,
                    p[n.info["probe_key"]], p.valid,
                )
                cols = dict(p.columns)
                cols.update(
                    ops.gather_payload(b, bidx, match, list(n.info["payload"]))
                )
                return Table(cols, match)
            raise TypeError(f"unstreamable physical node kind {n.kind!r}")

        ev.agg_dict = _agg_dict
        return ev

    def _merge(b: PNode, st, ev):
        """Fold one step's local partial of breaker ``b`` into its state."""
        t = ev(b.children[0])
        if b.kind == "aggregate":
            out = {}
            for name, e, kind in b.info["aggs"]:
                local = (
                    ops.sum_where(e.eval(t), t.valid)
                    if kind == "sum"
                    else ops.count_where(t.valid)
                )
                out[name] = st[name] + local[None].astype(st[name].dtype)
            return out
        if b.kind == "groupby_dense":
            res = ops.groupby_dense(
                b.info["key_expr"].eval(t),
                b.info["num_groups"],
                ev.agg_dict(t, b.info["aggs"]),
                t.valid,
            )
            return {
                name: st[name] + res[name][None].astype(st[name].dtype)
                for name in st
            }
        if b.kind == "groupby_sorted":
            key = b.info["key"]
            gkeys, gvalid, out = ops.groupby_sorted(
                t[key], t.valid, ev.agg_dict(t, b.info["aggs"])
            )
            C = st["keys"].shape[1]
            # the GroupByCombine path, incrementally: concat state with the
            # morsel partial, re-group by true key, re-SUM every agg (counts
            # are small exact integers in f32)
            ck = jnp.concatenate([st["keys"][0], gkeys])
            cv = jnp.concatenate([st["valid"][0], gvalid])
            caggs = {
                name: (
                    jnp.concatenate(
                        [st["aggs"][name][0], out[name].astype(jnp.float32)]
                    ),
                    "sum",
                )
                for name, _e, _k in b.info["aggs"]
            }
            mkeys, mvalid, mout = ops.groupby_sorted(ck, cv, caggs)
            # compact surviving groups into the fixed-capacity state (merged
            # arrays are at concat length, valid groups sit at group starts)
            rank = jnp.cumsum(mvalid.astype(jnp.int32)) - 1
            keep = mvalid & (rank < C)
            slot = jnp.where(keep, rank, C)
            new_keys = (
                jnp.zeros((C + 1,), jnp.int32)
                .at[slot]
                .set(jnp.where(keep, mkeys, 0))[:C]
            )
            new_valid = jnp.zeros((C + 1,), jnp.bool_).at[slot].set(keep)[:C]
            new_aggs = {
                name: jnp.zeros((C + 1,), jnp.float32)
                .at[slot]
                .set(jnp.where(keep, mout[name], 0.0))[:C][None]
                for name, _e, _k in b.info["aggs"]
            }
            over = st["overflow"][0] + (mvalid & ~keep).sum().astype(jnp.int32)
            return {
                "keys": new_keys[None],
                "valid": new_valid[None],
                "aggs": new_aggs,
                "overflow": over[None],
            }
        if b.kind == "topk":
            k = b.info["k"]
            vals, payload = ops.topk_rows(
                t[b.info["key"]], t.valid, k,
                {c: t[c] for c in b.info["payload"]},
            )
            cvals = jnp.concatenate([st["vals"][0], vals])
            top_vals, idx = jax.lax.top_k(cvals, k)
            new_payload = {
                c: jnp.concatenate(
                    [st["payload"][c][0],
                     payload[c].astype(st["payload"][c].dtype)]
                )[idx][None]
                for c in st["payload"]
            }
            return {"vals": top_vals[None], "payload": new_payload}
        raise NotImplementedError(b.kind)

    # ---- jitted steps ------------------------------------------------------
    check_vma = mux.pack_impl != "pallas" and num_pods == 1
    state_specs = jax.tree.map(lambda _: P(axes), states)
    res_specs = (P(axes),) * (2 * len(resident_prepped))

    def _resident_flats():
        flat = []
        for t in resident_prepped:
            flat.extend((t.columns, t.valid))
        return flat

    def _build_step(breakers: list[PNode], *, with_rows: bool,
                    spill_nodes: list[PNode], drain_node: PNode | None):
        """jit(shard_map) over (states, resident tables[, morsel/drain])."""
        spill_ids = {id(n) for n in spill_nodes}
        if drain_node is not None:
            spill_ids = {id(drain_node)}
        nspill = len(spill_ids)

        def body(st, *flat):
            drops: list[jax.Array] = []
            spills: dict[int, tuple] = {}
            reports: dict[str, jax.Array] = {}
            nres = 2 * len(resident_prepped)
            morsel = None
            drain_for = None
            if drain_node is not None:
                drain_for = (
                    id(drain_node), Table(dict(flat[nres]), flat[nres + 1])
                )
            elif with_rows:
                morsel = Table(dict(flat[nres]), flat[nres + 1])
            tabs = {
                name: Table(dict(flat[2 * i]), flat[2 * i + 1])
                for i, name in enumerate(resident_names)
            }
            tabs[streamed_name] = morsel
            ev = _make_ev(tabs, st, drops, spills, spill_ids, reports,
                          drain_for=drain_for)
            new = dict(st)
            for b in breakers:
                new[_bname(b)] = _merge(b, st[_bname(b)], ev)
            dropped = sum(drops) if drops else jnp.int32(0)
            spill_out = [spills[k] for k in sorted(spills)]
            return new, spill_out, dropped, reports

        extra_specs = ()
        if with_rows or drain_node is not None:
            extra_specs = (P(axes), P(axes))
        fn = shard_map(
            body,
            mesh=mesh,
            in_specs=(state_specs,) + res_specs + extra_specs,
            out_specs=(state_specs, [(P(axes), P(axes))] * nspill, P(), P()),
            check_vma=check_vma,
        )
        return jax.jit(fn)

    def _collect_spill(spill_out, width: int) -> np.ndarray:
        rows_list = []
        for rows, mask in spill_out:
            r = np.asarray(fetch(rows))
            m = np.asarray(fetch(mask))
            rows_list.append(r[m])
        if not rows_list:
            return np.zeros((0, width), np.int32)
        return np.concatenate(rows_list)

    drain_steps: dict = {}
    steps: dict = {}

    def _drain(p: int, node: PNode, breakers, pending: np.ndarray, st,
               drops_h, stats):
        """Re-offer spilled rows until the overflow partition drains dry."""
        schema = list(node.schema)
        key = (p, id(node))
        if key not in drain_steps:
            downstream = [
                b for b in breakers
                if any(id(x) == id(node)
                       for x in sp.shuffles_feeding(b, streamed_only=True))
            ]
            drain_steps[key] = _build_step(
                downstream, with_rows=False, spill_nodes=[], drain_node=node
            )
        step = drain_steps[key]
        rounds = 0
        while len(pending):
            if rounds >= MAX_DRAIN_ROUNDS:
                raise RuntimeError(
                    f"{plan.name}: spill drain did not converge after "
                    f"{rounds} rounds ({len(pending)} rows pending)"
                )
            rounds += 1
            take, pending = pending[:morsel_cap], pending[morsel_cap:]
            dt = from_numpy(
                {c: take[:, i].astype(np.int32) for i, c in enumerate(schema)}
            )
            dt = _prep(pad_to(dt, morsel_cap), num_shards)
            # drain-step reports are re-offers of already-counted rows, so
            # they stay out of the per-edge arrival histograms
            with maybe_span(tracer, "repro.drain", "stream", round=rounds,
                            pending_rows=int(len(take))):
                st, spill_out, dropped, _reports = step(
                    st, *_resident_flats(), dt.columns, dt.valid
                )
                jax.block_until_ready(st)
            drops_h.append(dropped)
            fresh = _collect_spill(spill_out, len(schema))
            if len(fresh):
                pending = (
                    np.concatenate([pending, fresh]) if len(pending) else fresh
                )
        stats["drain_rounds"] += rounds
        return st

    # ---- finalize ----------------------------------------------------------
    def _finalize_root(st):
        root = plan.root
        s = jax.tree.map(lambda x: np.asarray(fetch(x)), st[_bname(root)])
        if root.kind in ("aggregate", "groupby_dense"):
            return {
                name: s[name].sum(axis=0) for name, _e, _k in root.info["aggs"]
            }
        if root.kind == "topk":
            k = root.info["k"]
            vals = s["vals"].reshape(-1)
            order = np.argsort(-vals, kind="stable")[:k]
            out = {c: s["payload"][c].reshape(-1)[order] for c in s["payload"]}
            out["_valid"] = ~np.isneginf(vals[order])
            return out
        raise NotImplementedError(f"streamed root {root.kind}")

    def _check_group_overflow(st):
        for b in sp.breakers:
            if b.kind != "groupby_sorted":
                continue
            over = int(np.asarray(fetch(st[_bname(b)]["overflow"])).sum())
            if over:
                raise RuntimeError(
                    f"{plan.name}: group state overflowed by {over} groups on "
                    f"{_bname(b)}; raise group_state_rows (or the device "
                    "budget)"
                )

    # ---- per-edge arrival accumulation -------------------------------------
    # Shuffle edges whose input varies morsel-to-morsel: their per-step
    # histograms accumulate to ONE traversal of the stream per pass.  A
    # resident-side edge inside a streamed pass instead re-ships its whole
    # (unchanging) table every step — its traversal count is the step
    # count, and the byte model prices one shipment, so the report carries
    # the multiplier explicitly.
    streaming_edge_keys = set()

    def _mark_streaming(n: PNode, seen: set) -> None:
        if id(n) in seen:
            return
        seen.add(id(n))
        for c in n.children:
            _mark_streaming(c, seen)
        if (
            n.kind == "exchange"
            and n.info["exkind"] == "shuffle"
            and sp.streamed(n)
        ):
            streaming_edge_keys.add(report_keys[id(n)])

    _mark_streaming(plan.root, set())

    def _accumulate_reports(edge_hists, reports, p: int) -> None:
        """Fold one step's psum'd arrival counts into the per-(edge, pass)
        accumulators.  Keyed by pass: a shuffle shared across passes (Q17's
        lineitem shuffle feeds both) re-ships the stream per pass, so each
        traversal is measured against the model separately — summing them
        would read as 2x the modeled single-traversal bytes."""
        for k, h in reports.items():
            arr = np.asarray(fetch(h)).astype(np.int64)
            ek = (k, p)
            hist, n_steps = edge_hists.get(ek, (0, 0))
            edge_hists[ek] = (hist + arr, n_steps + 1)

    def _final_reports(edge_hists) -> dict:
        """Executor-shaped report dict from the accumulators.  Edges seen
        in one pass keep their base key; multi-pass edges split into
        ``<key>@p<pass>`` traversals.  Streamed plans never salt (salted
        plans refuse to stream), so overload is the plain-route arrival
        skew of the whole stream."""
        passes_of: dict[str, list[int]] = {}
        for k, p in edge_hists:
            passes_of.setdefault(k, []).append(p)
        out: dict = {}
        for (k, p), (h, n_steps) in sorted(edge_hists.items()):
            key = f"{k}@p{p}" if len(passes_of[k]) > 1 else k
            hist = h[:num_shards]
            over = float(hist.max()) * num_shards / max(int(hist.sum()), 1)
            out[key] = {
                "counts": h,
                "traversals": 1 if k in streaming_edge_keys else n_steps,
                "overload": over,
                "plain_overload": over,
                "salted": False,
            }
        return out

    # ---- the runner --------------------------------------------------------
    def run():
        st = states
        drops_h: list = []
        edge_hists: dict = {}
        stats = {
            "passes": sp.num_passes,
            "morsels": 0,
            "spilled_rows": 0,
            "drain_rounds": 0,
            "prefetch_wait_s": 0.0,
            "prefetch_total_s": 0.0,
        }
        for p, streamed_bs, resident_bs, spill_nodes in pass_plan:
            with maybe_span(tracer, "repro.pass", "stream", pass_idx=p,
                            streamed_breakers=len(streamed_bs),
                            resident_breakers=len(resident_bs)):
                if resident_bs:
                    key = (p, "resident")
                    if key not in steps:
                        steps[key] = _build_step(
                            resident_bs, with_rows=False, spill_nodes=[],
                            drain_node=None,
                        )
                    st, _, dropped, reports = steps[key](
                        st, *_resident_flats()
                    )
                    _accumulate_reports(edge_hists, reports, p)
                    drops_h.append(dropped)
                if not streamed_bs:
                    continue
                key = (p, "streamed")
                if key not in steps:
                    steps[key] = _build_step(
                        streamed_bs, with_rows=True, spill_nodes=spill_nodes,
                        drain_node=None,
                    )
                step = steps[key]
                pending = np.zeros((0, 0), np.int32)
                it = Prefetcher(
                    (_prep(chunk, num_shards) for chunk in src.chunks()),
                    depth=ctx.prefetch_depth,
                )
                t0 = time.perf_counter()
                wait = 0.0
                while True:
                    w0 = time.perf_counter()
                    try:
                        m = next(it)
                    except StopIteration:
                        wait += time.perf_counter() - w0
                        break
                    wait += time.perf_counter() - w0
                    stats["morsels"] += 1
                    with maybe_span(tracer, "repro.morsel", "stream",
                                    morsel=stats["morsels"], pass_idx=p):
                        st, spill_out, dropped, reports = step(
                            st, *_resident_flats(), m.columns, m.valid
                        )
                        # block on the fold: otherwise async dispatch returns
                        # instantly and the device compute queued here gets
                        # billed to the *next* ``next(it)`` wait, inverting
                        # the overlap measurement
                        jax.block_until_ready(st)
                    _accumulate_reports(edge_hists, reports, p)
                    drops_h.append(dropped)
                    if spill_nodes:
                        fresh = _collect_spill(
                            spill_out, len(spill_nodes[0].schema)
                        )
                        stats["spilled_rows"] += int(len(fresh))
                        pending = (
                            np.concatenate([pending, fresh])
                            if pending.size
                            else fresh
                        )
                stats["prefetch_wait_s"] += wait
                stats["prefetch_total_s"] += time.perf_counter() - t0
                if spill_nodes and len(pending):
                    st = _drain(
                        p, spill_nodes[0], streamed_bs, pending, st, drops_h,
                        stats,
                    )
        dropped_total = sum(int(fetch(d)) for d in drops_h)
        if dropped_total:
            _raise_on_dropped(plan.name, jnp.int32(dropped_total))
        _check_group_overflow(st)
        total = stats["prefetch_total_s"]
        stats["prefetch_overlap_fraction"] = (
            1.0 - stats["prefetch_wait_s"] / total if total > 0 else 0.0
        )
        return _finalize_root(st), stats, _final_reports(edge_hists)

    from ...obs.model_check import edge_models

    return _StreamedRunner(plan, run, edge_models(plan), tracer)


class _StreamedRunner(RunnerBase):
    """Zero-arg streamed runner.

    Unlike the in-memory :class:`~.executor.CompiledRunner`, streamed
    runners are built per call chain (never memoized), so they may hold the
    compile-time tracer and deposit into it directly.  ``.stats`` keeps the
    historical morsel/pass/spill/prefetch counters of the LAST run; the
    same numbers ride each run's :class:`QueryTrace` as ``counters``.
    """

    def __init__(self, plan, run_fn, models: dict, tracer):
        self._plan = plan
        self._run_fn = run_fn
        self._models = models
        self._tracer = tracer
        self.stats: dict = {}

    def __call__(self):
        from ...obs.model_check import build_query_trace

        t0 = time.perf_counter()
        result, stats, reports = self._run_fn()
        measured = time.perf_counter() - t0
        self.stats = stats
        qt = build_query_trace(
            self._plan, reports, self._models,
            counters={k: float(v) for k, v in stats.items()},
            measured_s=measured,
        )
        self._last_trace = qt
        deposit(self._tracer, qt)
        return result


__all__ = ["compile_plan_streamed", "BREAKER_KINDS"]
