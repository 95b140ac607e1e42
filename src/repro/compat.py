"""Thin wrappers over the JAX mesh API that every mesh-building module shares.

``make_mesh`` fixes the axis types to Auto, ``shard_map`` keeps the repo's
keyword spelling, ``enable_cpu_collectives`` switches on Gloo for the
multi-process CPU harness, and ``fetch`` reads arrays that span processes,
one ``repro.transfer`` span per device-to-host read.
"""

from __future__ import annotations

import os
from typing import Any, Sequence

import jax
import numpy as np

from .obs.trace import maybe_span


def make_mesh(
    axis_shapes: Sequence[int], axis_names: Sequence[str]
) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        axis_shapes,
        axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


def shard_map(
    f: Any,
    *,
    mesh: jax.sharding.Mesh,
    in_specs: Any,
    out_specs: Any,
    check_vma: bool = True,
    axis_names: Any = None,
):
    """``jax.shard_map``; ``axis_names`` (the axes the body is manual over)
    defaults to every mesh axis."""
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=check_vma,
        axis_names=frozenset(axis_names or ()),
    )


def enable_cpu_collectives() -> None:
    """Turn on cross-process CPU collectives (Gloo).

    Multi-process CPU runs (``launch/cluster.py``) need a CPU collectives
    backend — without one every cross-process psum/ppermute fails with
    "Multiprocess computations aren't implemented on the CPU backend".
    Must run before the CPU backend is initialized (i.e. before any device
    query).
    """
    os.environ.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def fetch(x: Any):
    """Concrete numpy value of an array that may span multiple processes.

    Addressable or fully replicated arrays read directly; an array sharded
    over devices this process cannot address is all-gathered across
    processes.  Pytrees are mapped leaf-wise, and each device array's read
    is one ``repro.transfer`` span.
    """

    def one(leaf):
        if not hasattr(leaf, "sharding"):  # numpy / python scalar
            return np.asarray(leaf)
        with maybe_span(None, "repro.transfer", bytes=leaf.nbytes):
            if leaf.is_fully_addressable or leaf.is_fully_replicated:
                return np.asarray(leaf)
            from jax.experimental import multihost_utils

            return np.asarray(
                multihost_utils.process_allgather(leaf, tiled=True)
            )

    return jax.tree.map(one, x)


__all__ = ["make_mesh", "shard_map", "enable_cpu_collectives", "fetch"]
