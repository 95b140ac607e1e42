"""The communication multiplexer (paper §3.2.2).

The paper gives each server ONE dedicated network endpoint that all local
exchange operators talk to; only multiplexers are interconnected
(``n(n-1)`` connections instead of ``n^2 t^2 - t``), messages come from a
reusable registered pool (zero-copy RDMA), are NUMA-local, and are sent
according to the round-robin schedule.

The JAX rendition is a thin object that carries the per-mesh communication
*policy* — which schedule, which collective strategy per network level —
so that models and the relational engine never choose transports themselves
(they are "decoupled": they see only this interface).  Concretely:

* message pool / zero-copy  -> ``donate_buffers`` jit wrapper + the
  streaming ``shuffle_consume`` (one chunk in flight, reused accumulator);
* NUMA-aware allocation     -> chunk layouts are kept shard-local; nothing
  is gathered to a single device;
* dedicated network thread  -> XLA's async DMA engine; phases are issued
  back-to-back so the DMA engine stays busy while the VPU/MXU computes.

Beyond the transport (``impl``), the multiplexer carries the partition/pack
policy for :meth:`CommMultiplexer.hash_shuffle`:

* ``pack_impl`` — ``"xla"`` (one-hot/cumsum reference) or ``"pallas"`` (the
  fused partition+pack kernel; no ``[rows, num_dest]`` intermediate).  Both
  produce bit-identical buffers, counts, and drop counts.
* ``pipeline_chunks`` — split the shuffle into this many row chunks and
  double-buffer: pack chunk ``k + 1`` while chunk ``k``'s phases ship.
  Must divide both the row count and the capacity of every shuffle routed
  through this multiplexer; a shuffle it does not divide runs unchunked
  (with a warning) rather than failing.
* ``transport_chunks`` — split each scheduled phase's message into this many
  independent ppermutes (finer-grained DMA pipelining).  Must divide the
  per-chunk capacity; falls back to whole messages (with a warning)
  otherwise.  The monolithic ``"xla"`` transport ignores it.

None of the knobs changes *what* is delivered — only how it is packed and
phased; ``tests/test_exchange_equiv.py`` holds every combination to the same
results.  Capacity overflow is likewise policy-free: ``hash_shuffle``
returns a psum'd ``dropped`` count and the relational layer raises on any
nonzero value (PR 1's overflow-raises contract) — rows are never silently
lost.

Knob values come from one of two places: explicit arguments to
:func:`make_multiplexer` (benchmarks, A/B tests), or — the default on the
query paths — the topology-driven autotuner
(:func:`repro.core.autotune.tune_multiplexer` via
``make_multiplexer(auto=True, table_stats=...)``), which minimizes the
modeled pack+shuffle makespan for the actual message sizes and mesh.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import warnings
from typing import Any, Callable, Sequence

import jax

from . import exchange
from .hybrid import HybridPlan, plan_for_mesh
from .schedule import make_schedule, verify_schedule
from .topology import ChipSpec, V5E


@dataclasses.dataclass(frozen=True)
class CommMultiplexer:
    """Per-mesh communication policy object.

    ``impl`` selects the shuffle transport: ``"round_robin"`` (the paper's
    scheduled phases), ``"one_factorization"`` (bidirectional pairing), or
    ``"xla"`` (monolithic all-to-all baseline — the 'unscheduled' transport
    the paper improves on).  ``pack_impl``/``pipeline_chunks``/
    ``transport_chunks`` tune the partition+pack hot path (module docstring).
    """

    plan: HybridPlan
    impl: exchange.AllToAllImpl = "round_robin"
    pack_impl: exchange.PackImpl = "xla"
    pipeline_chunks: int = 1
    transport_chunks: int = 1
    # Two-level meshes: how broadcast-style build sides cross the pod axis
    # ("broadcast" replicates over DCI, "reshard" hash-exchanges them like
    # the probe side).  Set by the autotuner; ignored on single-pod meshes.
    cross_pod: str = "broadcast"

    def describe(self) -> dict:
        """JSON-able knob summary — what actually carries the traffic.
        Trace exports attach this so a Perfetto timeline (or a bench
        record) names the transport/pack schedule its exchanges rode."""
        return dict(
            impl=str(self.impl),
            pack_impl=str(self.pack_impl),
            pipeline_chunks=int(self.pipeline_chunks),
            transport_chunks=int(self.transport_chunks),
            cross_pod=str(self.cross_pod),
            small_axes=list(self.plan.small_axes),
            large_axes=list(self.plan.large_axes),
            num_pods=int(self.plan.num_pods),
        )

    # -- exchange-operator entry points (must be inside shard_map) ---------

    def all_to_all(self, x: jax.Array, axis_name: str) -> jax.Array:
        self.plan.validate_axis_for_alltoall(axis_name)
        transport = self.transport_chunks
        if transport > 1 and (x.ndim < 2 or x.shape[1] % transport):
            warnings.warn(
                f"transport_chunks={transport} does not divide message dim of "
                f"shape {x.shape}; shipping whole messages",
                stacklevel=2,
            )
            transport = 1
        return exchange.all_to_all(
            x, axis_name, impl=self.impl, num_chunks=transport
        )

    def _resolve_transport(self, message_dim: int) -> int:
        """Transport sub-chunking that divides ``message_dim`` (else 1)."""
        transport = self.transport_chunks
        if transport > 1 and message_dim % transport:
            warnings.warn(
                f"transport_chunks={transport} does not divide message dim "
                f"{message_dim}; shipping whole messages",
                stacklevel=3,
            )
            transport = 1
        return transport

    # -- token routing: the one exchange fabric -----------------------------

    def dispatch(self, x: jax.Array, axis_name: str) -> jax.Array:
        """All-to-all token dispatch over the WHOLE mesh, pod axis included.

        On a single-level mesh this is exactly :meth:`all_to_all` over
        ``axis_name``.  On a two-level mesh the leading dim must span the
        JOINT ``(pod, axis_name)`` axis (``N = P * n``, mesh device order)
        and the route is :func:`repro.core.exchange.dispatch_two_level`:
        one coarse message per peer pod over the slow network, then the
        fine in-pod scheduled all-to-all — the same two hops as
        :meth:`hash_shuffle_global`, generalized beyond hash keys to any
        caller-assigned destination layout (MoE expert dispatch).  Both
        hops are pure permutations, so the result is bit-identical to a
        flat all-to-all over the joint axis.
        """
        pod = self.plan.pod_axis
        if pod is None:
            return self.all_to_all(x, axis_name)
        self.plan.validate_axis_for_alltoall(axis_name)
        transport = self._resolve_transport(
            self.plan.num_pods * math.prod(x.shape[1:])
        )
        return exchange.dispatch_two_level(
            x, axis_name, pod, impl=self.impl, num_chunks=transport
        )

    def combine(self, x: jax.Array, axis_name: str) -> jax.Array:
        """The return trip of :meth:`dispatch` (fine in-pod hop first, then
        one coarse message per peer pod).  Same flat-all-to-all contract,
        same bit-identity guarantee."""
        pod = self.plan.pod_axis
        if pod is None:
            return self.all_to_all(x, axis_name)
        self.plan.validate_axis_for_alltoall(axis_name)
        transport = self._resolve_transport(
            self.plan.num_pods * math.prod(x.shape[1:])
        )
        return exchange.combine_two_level(
            x, axis_name, pod, impl=self.impl, num_chunks=transport
        )

    def shuffle_consume(
        self,
        x: jax.Array,
        axis_name: str,
        consume: Callable[[Any, jax.Array, jax.Array], Any],
        init: Any,
    ) -> Any:
        """Streaming shuffle; overlaps phase k+1 comm with phase k compute."""
        self.plan.validate_axis_for_alltoall(axis_name)
        if self.impl == "xla":
            # No phases to stream over: materialize then fold.
            y = exchange.xla_all_to_all(x, axis_name)
            acc = init
            for j in range(x.shape[0]):
                acc = consume(acc, y[j], j)
            return acc
        sched = "shift" if self.impl == "round_robin" else self.impl
        return exchange.scheduled_all_to_all_consume(
            x, axis_name, consume, init, schedule=sched
        )

    def _resolve_chunks(self, rows: int, capacity: int) -> tuple[int, int]:
        """Chunk knobs that actually divide this shuffle's shapes, warning
        and falling back (unchunked / whole messages) where they do not."""
        chunks = self.pipeline_chunks
        if chunks > 1 and (rows % chunks or capacity % chunks):
            warnings.warn(
                f"pipeline_chunks={chunks} does not divide rows={rows} / "
                f"capacity={capacity}; running this shuffle unchunked",
                stacklevel=3,
            )
            chunks = 1
        transport = self.transport_chunks
        if transport > 1 and (capacity // chunks) % transport:
            warnings.warn(
                f"transport_chunks={transport} does not divide per-chunk "
                f"capacity {capacity // chunks}; shipping whole messages",
                stacklevel=3,
            )
            transport = 1
        return chunks, transport

    def hash_shuffle(
        self,
        keys: jax.Array,
        rows: jax.Array,
        axis_name: str,
        capacity: int,
        valid: jax.Array | None = None,
    ):
        self.plan.validate_axis_for_alltoall(axis_name)
        chunks, transport = self._resolve_chunks(keys.shape[0], capacity)
        return exchange.hash_shuffle(
            keys, rows, axis_name, capacity, impl=self.impl, valid=valid,
            pack_impl=self.pack_impl, num_chunks=chunks,
            transport_chunks=transport,
        )

    def hash_shuffle_spill(
        self,
        keys: jax.Array,
        rows: jax.Array,
        axis_name: str,
        capacity: int,
        valid: jax.Array | None = None,
    ):
        """Capacity-bounded exchange that flags overflow instead of dropping.

        Returns ``(rows_out, valid_out, spilled)`` with ``spilled`` a
        sender-local per-row mask; the caller parks those rows in a
        host-memory overflow partition and drains them later
        (``relational.planner.stream``).  Single-level meshes only: on a pod
        mesh the streamed executor sizes messages for zero drop instead,
        because the two-level hop re-packs rows mid-flight and the sender
        can no longer name its spilled rows.
        """
        if self.plan.pod_axis is not None:
            raise NotImplementedError(
                "spill-capable exchange is single-level only; pod meshes "
                "must size streamed exchanges for zero drop"
            )
        self.plan.validate_axis_for_alltoall(axis_name)
        return exchange.hash_shuffle_spill(
            keys, rows, axis_name, capacity, impl=self.impl, valid=valid,
            pack_impl=self.pack_impl,
        )

    def broadcast(self, x: jax.Array, axis_name: str) -> jax.Array:
        impl = "xla" if self.impl == "xla" else "ring"
        return exchange.broadcast_exchange(x, axis_name, impl=impl)

    # -- global (two-level) exchange entry points ---------------------------

    def hash_shuffle_global(
        self,
        keys: jax.Array,
        rows: jax.Array,
        axis_name: str,
        capacity: int,
        valid: jax.Array | None = None,
    ):
        """Repartition by key hash over the WHOLE mesh, pod axis included.

        On a single-level mesh this is exactly :meth:`hash_shuffle`.  On a
        two-level mesh it runs the sanctioned coarse route
        (:func:`repro.core.exchange.hash_shuffle_two_level`): one message
        per peer pod over the slow network, then the fine in-pod shuffle
        over ``axis_name`` — the multiplexer-granularity exchange of paper
        §3.2.2.  The plan still rejects ``axis_name`` being the pod axis
        itself (that would be a fine-grained DCI shuffle).
        """
        pod = self.plan.pod_axis
        if pod is None:
            return self.hash_shuffle(keys, rows, axis_name, capacity, valid)
        self.plan.validate_axis_for_alltoall(axis_name)
        chunks, transport = self._resolve_chunks(
            keys.shape[0] * self.plan.num_pods, capacity * self.plan.num_pods
        )
        return exchange.hash_shuffle_two_level(
            keys, rows, axis_name, pod, capacity, impl=self.impl,
            valid=valid, pack_impl=self.pack_impl, num_chunks=chunks,
            transport_chunks=transport,
        )

    def broadcast_global(self, x: jax.Array, axis_name: str) -> jax.Array:
        """Every device ends with every device's chunk, pods included.

        In-pod ring all-gather first (fast network), then one coarse
        all-gather of the pod-aggregated block over the pod axis — each byte
        crosses DCI once per remote pod, at pod granularity.  Result leading
        dims are ``[num_pods, n]`` on a two-level mesh, ``[n]`` otherwise
        (callers flatten; every device holds an identical copy either way).
        The two hops run under ``in_pod`` and ``cross_pod`` scopes.
        """
        pod = self.plan.pod_axis
        if pod is None:
            return self.broadcast(x, axis_name)
        with jax.named_scope("in_pod"):
            y = self.broadcast(x, axis_name)
        impl = "xla" if self.impl == "xla" else "ring"
        with jax.named_scope("cross_pod"):
            return exchange.broadcast_exchange(y, pod, impl=impl)

    # -- gradient sync (hybrid two-level vs flat) ---------------------------

    def psum_tree(self, tree: Any, data_axes: tuple[str, ...]) -> Any:
        """All-reduce a gradient tree over the data-parallel axes.

        Hierarchical (RS-in-pod -> AR-cross-pod -> AG-in-pod) when the plan
        has a large-network axis; flat otherwise.
        """
        if self.plan.grad_sync == "hierarchical" and len(data_axes) >= 2:
            outer = [a for a in data_axes if a in self.plan.large_axes]
            inner = [a for a in data_axes if a not in self.plan.large_axes]
            if outer and inner:
                return exchange.hierarchical_psum_tree(tree, inner[0], outer[0])
        return exchange.flat_psum_tree(tree, data_axes)


# one_factorization->shift downgrade warnings already issued, keyed by the
# offending axis sizes — a long-lived process builds a multiplexer per query,
# and repeating the identical warning every time is pure noise.  (Tests that
# assert the warning clear this set first.)
_warned_odd_axis_sizes: set[tuple[int, ...]] = set()


def resolve_schedule_impl(
    impl: exchange.AllToAllImpl, small_axis_sizes: Sequence[int]
) -> exchange.AllToAllImpl:
    """Downgrade an impl that cannot run on the given shuffle-axis sizes.

    ``one_factorization`` (the round-robin-tournament pairing) only exists
    for even ``n``; on a mesh with an odd-sized shuffle axis the schedule
    constructor would raise at trace time, *inside* the first query.  Fall
    back to the ``shift`` schedule (valid for every ``n``, and what the
    paper itself uses) at multiplexer-build time instead, with a warning —
    issued once per distinct set of odd axis sizes, not per call.
    """
    if impl == "one_factorization" and any(
        s > 1 and s % 2 for s in small_axis_sizes
    ):
        odd = tuple(s for s in small_axis_sizes if s > 1 and s % 2)
        if odd not in _warned_odd_axis_sizes:
            _warned_odd_axis_sizes.add(odd)
            warnings.warn(
                f"one_factorization schedules need even axis sizes, got "
                f"{list(odd)}; falling back to the round_robin (shift) "
                "schedule",
                stacklevel=3,
            )
        return "round_robin"
    return impl


def make_multiplexer(
    mesh: jax.sharding.Mesh,
    impl: exchange.AllToAllImpl = "round_robin",
    pack_impl: exchange.PackImpl = "xla",
    pipeline_chunks: int = 1,
    transport_chunks: int = 1,
    auto: bool = False,
    table_stats=None,
    chip: ChipSpec = V5E,
    topology: str = "ring",
    refine: bool = False,
    broadcast_stats=None,
    cross_pod: str = "broadcast",
) -> CommMultiplexer:
    """Build the multiplexer for a mesh; verifies the schedule once (cheap).

    Mirrors the paper's startup step of establishing the multiplexer
    connections before query processing begins.  Every small (shuffle-
    eligible) axis's schedule is verified here — an impl the mesh cannot
    support is downgraded by :func:`resolve_schedule_impl` rather than
    letting an invalid config reach the runtime.

    With ``auto=True`` (or ``impl="auto"``) every knob — transport,
    ``pack_impl``, ``pipeline_chunks``, ``transport_chunks``, and on
    two-level meshes the ``cross_pod`` build-side strategy — is derived
    from the :mod:`repro.core.topology` cost model by
    :func:`repro.core.autotune.tune_multiplexer` instead of taken from the
    arguments.  ``table_stats`` (one :class:`repro.core.autotune.TableStats`
    per exchange the multiplexer will carry) is required;
    ``broadcast_stats`` optionally describes a broadcast-style join's build
    side so the tuner can price cross-pod broadcast vs reshard; ``chip`` /
    ``topology`` select the hardware model and ``refine=True`` additionally
    micro-benchmarks the best modeled candidates on the live mesh.
    """
    if auto or impl == "auto":
        from .autotune import tune_multiplexer

        if table_stats is None:
            raise ValueError(
                "make_multiplexer(auto=True) needs table_stats — the "
                "rows/row_bytes of the exchanges this multiplexer will carry"
            )
        tuned = tune_multiplexer(
            mesh, table_stats, chip=chip, topology=topology, refine=refine,
            broadcast_stats=broadcast_stats,
        )
        impl = tuned.impl
        pack_impl = tuned.pack_impl
        pipeline_chunks = tuned.pipeline_chunks
        transport_chunks = tuned.transport_chunks
        if tuned.cross_pod is not None:
            cross_pod = tuned.cross_pod
    plan = plan_for_mesh(
        tuple(mesh.axis_names), tuple(mesh.devices.shape), exchange=(
            "xla" if impl == "xla" else "round_robin"
        )
    )
    small_sizes = [
        size
        for ax, size in zip(mesh.axis_names, mesh.devices.shape)
        if ax not in plan.large_axes
    ]
    impl = resolve_schedule_impl(impl, small_sizes)
    if impl != "xla":
        kind = "shift" if impl == "round_robin" else impl
        for size in small_sizes:
            if size > 1:
                verify_schedule(make_schedule(size, kind))
    if cross_pod not in ("broadcast", "reshard"):
        raise ValueError(f"unknown cross_pod strategy {cross_pod!r}")
    return CommMultiplexer(
        plan=plan,
        impl=impl,
        pack_impl=pack_impl,
        pipeline_chunks=pipeline_chunks,
        transport_chunks=transport_chunks,
        cross_pod=cross_pod,
    )


# ----------------------------------------------------------------------------
# Ambient multiplexer: lets code that cannot take a mux argument (the MoE
# layer inside a model's decode step) still route its exchanges through the
# session's tuned policy object.
# ----------------------------------------------------------------------------

_ACTIVE_MUX: list[CommMultiplexer] = []


@contextlib.contextmanager
def use_multiplexer(mux: CommMultiplexer):
    """Make ``mux`` the ambient multiplexer inside the with-block.

    The serving engine wraps its decode loop in this so the expert-parallel
    dispatch (``models/moe.py``) traces against the engine's auto-tuned
    multiplexer — same schedules as the relational exchanges — without
    threading a mux through the uniform model API.  Consulted at TRACE time:
    jit caches compiled under one mux are only reused within the same knobs
    (the engine owns both the mux and its jitted callables, so this holds).
    """
    _ACTIVE_MUX.append(mux)
    try:
        yield mux
    finally:
        _ACTIVE_MUX.pop()


def current_multiplexer() -> CommMultiplexer | None:
    """The innermost :func:`use_multiplexer` mux, or None."""
    return _ACTIVE_MUX[-1] if _ACTIVE_MUX else None


def donate_buffers(fn: Callable, argnums: tuple[int, ...]) -> Callable:
    """Message-pool discipline: reuse communication buffers across calls.

    The paper registers RDMA memory regions once and recycles them through a
    pool because registration (pinning) is expensive.  XLA's analogue is
    buffer donation: the donated input's device memory is reused for outputs,
    so steady-state steps allocate nothing.
    """
    return jax.jit(fn, donate_argnums=argnums)


__all__ = [
    "CommMultiplexer",
    "make_multiplexer",
    "resolve_schedule_impl",
    "use_multiplexer",
    "current_multiplexer",
    "donate_buffers",
]
