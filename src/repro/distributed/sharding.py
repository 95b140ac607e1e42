"""Logical-axis sharding rules + mesh context (MaxText-style, paper-aware).

Model code never names mesh axes directly.  It tags tensor dimensions with
*logical* names (``"batch"``, ``"heads"``, ``"d_ff"``, ``"experts"``, ...)
through :func:`shard`; an :class:`AxisRules` table maps logical names to mesh
axes.  On a CPU smoke test (no mesh context) everything is a no-op, so the
same model code runs single-device and on the 512-chip dry-run mesh.

The default rules encode the paper's hybrid-parallelism policy:

* coarse data parallelism crosses the slow network: ``batch -> (pod, data)``,
* fine model parallelism stays on the fast network: ``heads/d_ff/experts ->
  model`` (never ``pod``),
* the expert shuffle (the paper's all-to-all exchange) runs over ``model``
  only — parallel units for the exchange are the ``model``-axis devices,
  not every (pod, data, model) lane, which is exactly the paper's
  "n servers, not n x t threads" argument.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Iterator, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Logical dimension names used across the model zoo.
LOGICAL_AXES = (
    "batch",      # global batch                      -> (pod, data)
    "seq",        # sequence (attention q/k/v)        -> None
    "seq_sp",     # residual-stream seq (Megatron SP)  -> None | model
    "kv_seq",     # KV-cache sequence at decode       -> model (flash-decode)
    "d_model",    # residual stream                   -> None
    "heads",      # attention query heads             -> model
    "kv_heads",   # attention kv heads                -> model (if divisible)
    "d_ff",       # MLP hidden                        -> model
    "experts",    # MoE expert dim                    -> model (EP)
    "vocab",      # embedding/logits vocab            -> model
    "fsdp",       # parameter FSDP dim                -> data
    "expert_fsdp",# expert-weight inner dims           -> data (or model)
    "conv_dim",   # mamba conv channels               -> model
    "ssm_heads",  # mamba value heads                 -> model
)


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Mapping logical axis name -> mesh axis (or tuple of axes, or None).

    ``allow_uneven``: keep a sharding constraint even when the dimension is
    not divisible by the mesh factor (GSPMD pads).  Off by default — the
    §Perf hillclimb enables it for the 36/40/12-head archs, where dropping
    the constraint makes XLA replicate the whole attention block.
    """

    table: Mapping[str, tuple[str, ...] | str | None]
    allow_uneven: bool = False

    def spec_for(self, *names: str | None) -> P:
        return P(*[self.table.get(n) if n else None for n in names])

    def replace(self, **kw) -> "AxisRules":
        uneven = kw.pop("allow_uneven", self.allow_uneven)
        t = dict(self.table)
        t.update(kw)
        return AxisRules(t, allow_uneven=uneven)


def default_rules(multi_pod: bool) -> AxisRules:
    batch = ("pod", "data") if multi_pod else ("data",)
    return AxisRules(
        {
            "batch": batch,
            "seq": None,
            "seq_sp": None,
            "kv_seq": "model",
            "d_model": None,
            "heads": "model",
            "kv_heads": "model",
            "d_ff": "model",
            "experts": "model",
            "vocab": "model",
            "fsdp": "data",
            "expert_fsdp": "data",
            "conv_dim": "model",
            "ssm_heads": "model",
        }
    )


@dataclasses.dataclass(frozen=True)
class MeshContext:
    """Everything the model zoo needs to know about the machine.

    ``exchange_impl`` selects the all-to-all transport for the MoE/relational
    exchange (the paper's knob): ``"round_robin"`` (scheduled phases),
    ``"one_factorization"``, or ``"xla"`` (unscheduled baseline).
    """

    mesh: Mesh
    rules: AxisRules
    exchange_axis: str = "model"  # mesh axis the decoupled exchange runs over
    data_axes: tuple[str, ...] = ("data",)
    pod_axis: str | None = None  # set on multi-pod meshes
    exchange_impl: str = "round_robin"

    @property
    def exchange_size(self) -> int:
        return self.mesh.shape[self.exchange_axis]


_CTX: contextvars.ContextVar[MeshContext | None] = contextvars.ContextVar(
    "repro_mesh_context", default=None
)


def current_mesh_context() -> MeshContext | None:
    return _CTX.get()


@contextlib.contextmanager
def mesh_context(ctx: MeshContext | None) -> Iterator[MeshContext | None]:
    token = _CTX.set(ctx)
    try:
        if ctx is not None:
            with jax.set_mesh(ctx.mesh):
                yield ctx
        else:
            yield None
    finally:
        _CTX.reset(token)


def _divisible(
    dim: int, mesh: Mesh, axes: tuple[str, ...] | str | None, allow_uneven: bool
) -> bool:
    if axes is None:
        return True
    if isinstance(axes, str):
        axes = (axes,)
    k = 1
    for a in axes:
        k *= mesh.shape[a]
    if dim % k == 0:
        return True
    # uneven mode: keep the constraint as long as every shard gets >= 1 row
    # (GSPMD pads) — dropping it makes XLA replicate the whole operand chain
    return allow_uneven and dim >= k


def logical_sharding(
    shape: Sequence[int],
    *names: str | None,
    ctx: MeshContext | None = None,
    strict: bool = False,
) -> NamedSharding | None:
    """NamedSharding for a logical-tagged shape; None when no mesh context.

    Drops any logical axis whose mesh factor does not divide the dimension
    (e.g. 36 heads on a 16-way ``model`` axis) unless
    ``ctx.rules.allow_uneven`` — GSPMD pads uneven *internal* constraints.
    ``strict=True`` (argument shardings for jit ``in_shardings``) always
    requires exact divisibility: pjit rejects uneven argument shardings.
    """
    ctx = ctx or current_mesh_context()
    if ctx is None:
        return None
    assert len(shape) == len(names), (shape, names)
    uneven = ctx.rules.allow_uneven and not strict
    resolved = []
    used: set[str] = set()
    for dim, name in zip(shape, names):
        axes = ctx.rules.table.get(name) if name else None
        if isinstance(axes, str):
            axes = (axes,)
        if axes:
            # a mesh axis can shard at most one dim: leftmost logical name
            # wins -- under ZeRO-3 rules batch takes both axes and the
            # heads/d_ff constraints on the same tensor drop automatically,
            # while parameter specs (no batch dim) keep their mapping.
            axes = tuple(a for a in axes if a not in used)
        if not axes:
            resolved.append(None)
            continue
        if _divisible(dim, ctx.mesh, axes, uneven):
            used.update(axes)
            resolved.append(axes if len(axes) > 1 else axes[0])
        else:
            resolved.append(None)
    return NamedSharding(ctx.mesh, P(*resolved))


def is_spec_leaf(x) -> bool:
    """Spec trees use tuples of logical-axis names as leaves."""
    return isinstance(x, tuple) and (
        len(x) == 0 or all(n is None or isinstance(n, str) for n in x)
    )


def build_shardings(spec_tree, shape_tree, ctx: MeshContext | None = None):
    """NamedSharding tree from (logical spec tree, ShapeDtypeStruct tree).

    Used for jit argument shardings -> strict divisibility (pjit rejects
    padded argument shardings; uneven placement happens via internal
    constraints instead).
    """
    ctx = ctx or current_mesh_context()
    if ctx is None:
        return None
    return jax.tree.map(
        lambda spec, shp: logical_sharding(shp.shape, *spec, ctx=ctx, strict=True),
        spec_tree,
        shape_tree,
        is_leaf=is_spec_leaf,
    )


def shard(x: jax.Array, *names: str | None) -> jax.Array:
    """Tag an activation with logical axes (with_sharding_constraint)."""
    s = logical_sharding(x.shape, *names)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)


__all__ = [
    "LOGICAL_AXES",
    "AxisRules",
    "default_rules",
    "MeshContext",
    "current_mesh_context",
    "mesh_context",
    "logical_sharding",
    "is_spec_leaf",
    "build_shardings",
    "shard",
]
