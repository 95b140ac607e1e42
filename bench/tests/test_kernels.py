"""The pack kernel's bytes, from shapes, against the shapes the kernel is
called with, and the calls a four-shard plan makes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kernels
from repro.kernels import ops
from repro.relational.planner import tpch


def pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from pallas_calls(sub)


def nbytes(avals) -> int:
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in avals)


@pytest.mark.parametrize("rows, partitions", [(1000, 4), (4096, 4), (6000, 8), (300, 200)])
def test_pack_bytes_match_kernel_shapes(rows, partitions):
    keys = jnp.zeros((rows,), jnp.int32)
    valid = jnp.ones((rows,), jnp.int32)
    with ops.use_kernels(True):
        jaxpr = jax.make_jaxpr(
            lambda k, v: ops.hash_partition_ranks(k, v, partitions)
        )(keys, valid).jaxpr
    (call,) = list(pallas_calls(jaxpr))
    moved = nbytes(v.aval for v in call.invars) + nbytes(v.aval for v in call.outvars)
    assert kernels.PackCall(rows, partitions).bytes == moved


def test_four_shard_plan_packs_every_shuffle_in_chunks():
    catalog = tpch.tpch_catalog(0.01)
    plan = tpch.q17().plan(catalog, 4)
    shuffles = []

    def walk(n, seen=set()):
        if id(n) in seen:
            return
        seen.add(id(n))
        if n.kind == "exchange" and n.info["exkind"] == "shuffle":
            shuffles.append(n.children[0].cap)
        for c in n.children:
            walk(c)

    walk(plan.root)
    calls = kernels.plan_pack_calls(plan, "pallas", 4)
    assert shuffles and len(calls) == sum(4 if r % 4 == 0 else 1 for r in shuffles)
    assert sum(c.rows for c in calls) == sum(shuffles)
    assert kernels.plan_pack_calls(plan, "xla", 4) == []
    assert kernels.plan_pack_calls(tpch.q17().plan(catalog, 1), "pallas", 4) == []
