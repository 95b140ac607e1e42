"""Compiles for a described TPU v5e (2x2 host): nothing runs, but the TPU
compiler's refusals — unaligned tiles, unsupported kernel primitives, more
memory than a chip holds — show up here rather than on the chip.

The topology is described inside a fixture (never while a module is
imported), and every compile in this file runs with the persistent
compilation cache off: an executable compiled for a described chip cannot be
read back without one.
"""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

# SF 4 orders (6M rows), or SF 1 lineitem, over four chips: 1.5M rows,
# padded to whole 256-row kernel blocks as the exchange's wrappers do.
ROWS = -(-1_500_000 // 256) * 256
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("num_partitions", [4, 8, 256])
def test_hash_partition_pack_compiles(one_chip, num_partitions):
    from repro.kernels.hash_partition import hash_partition_pack

    x = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda k, v: hash_partition_pack(k, v, num_partitions, interpret=False)
    ).lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("num_partitions", [4, 8, 256])
def test_partition_pack_compiles(one_chip, num_partitions):
    from repro.kernels.hash_partition import partition_pack

    x = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda d: partition_pack(d, num_partitions + 1, interpret=False)
    ).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_q3_executor_compiles_on_four_chips(topo, monkeypatch):
    """A planned Q3 at SF 4 with the Pallas pack, on a 4-way mesh of the
    described chips.  The executor builds its mesh from ``jax.devices()``,
    places real tables and asks the backend whether to interpret kernels;
    the test steers those three to the described chips and to shapes."""
    from repro.kernels import ops
    from repro.relational import datagen
    from repro.relational.context import ExecutionContext
    from repro.relational.planner import executor as ex
    from repro.relational.planner import tpch
    from repro.relational.table import Table

    n = 4
    mesh = Mesh(np.array(topo.devices[:n]), (ex.SHUFFLE_AXIS,))

    def place(table, num_shards, mesh, axes):
        cap = math.ceil(table.capacity / num_shards) * num_shards
        sharding = NamedSharding(mesh, P(axes))

        def shape(x):
            return jax.ShapeDtypeStruct((cap,), x.dtype, sharding=sharding)

        return {c: shape(v) for c, v in table.columns.items()}, shape(table.valid)

    monkeypatch.setattr(ex, "_mesh", lambda num_shards, num_pods=1: mesh)
    monkeypatch.setattr(ex, "_place", place)
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    pq = tpch.q3()
    catalog = tpch.tpch_catalog(4)
    schema = datagen.gen_all(0.001)  # column names and dtypes only
    tables = {
        name: Table(
            {
                c: jax.ShapeDtypeStruct((catalog[name],), v.dtype)
                for c, v in schema[name].columns.items()
            },
            jax.ShapeDtypeStruct((catalog[name],), jnp.bool_),
        )
        for name in pq.tables
    }
    run = ex.compile_plan(
        pq.plan(catalog, n), tables, ExecutionContext(num_shards=n, pack_impl="pallas")
    )
    compiled = run._jfn.lower(*run._flat).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    per_device = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    assert 0 < per_device < HBM_BYTES, per_device


# SF 1 lineitem on one chip, and the columns Q1 reads.
LINEITEM_SF1 = 6_000_000
Q1_COLUMNS = (
    "l_shipdate", "l_returnflag", "l_linestatus", "l_extendedprice",
    "l_discount", "l_tax", "l_quantity",
)


def _has_scatter(compiled) -> bool:
    # The instruction, not the word: ``scatter`` also names source frames.
    return "scatter(" in compiled.as_text()


def test_q1_dense_groupby_compiles_without_scatter(one_chip):
    """Q1's six groups take the compare-and-reduce path: no scatter-add,
    and no matmul (the MXU would round the sums' operands to bfloat16)."""
    from repro.relational import queries
    from repro.relational.table import Table

    col = jax.ShapeDtypeStruct((LINEITEM_SF1,), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((LINEITEM_SF1,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(
        lambda cols, v: queries.q1_local(Table(dict(zip(Q1_COLUMNS, cols)), v))
    ).lower([col] * len(Q1_COLUMNS), valid).compile()
    assert not _has_scatter(compiled)
    assert not any(op in compiled.as_text() for op in (" dot(", " convolution("))


def test_dense_groupby_above_compare_limit_scatters(one_chip):
    from repro.relational import operators as ops

    num_groups = ops.DENSE_COMPARE_MAX_GROUPS + 1
    gid = jax.ShapeDtypeStruct((LINEITEM_SF1,), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((LINEITEM_SF1,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(
        lambda g, v: ops.groupby_dense(
            g, num_groups, {"s": (g, "sum"), "n": (g, "count")}, v
        )
    ).lower(gid, valid).compile()
    assert _has_scatter(compiled)
