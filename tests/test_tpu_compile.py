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
import re

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


def _compile_sf4(topo, monkeypatch, pq, num_pods: int = 1):
    """A planned template at SF 4 with the Pallas pack, on the described
    2x2 as a flat 4-way mesh or a ``(pod, q)`` mesh.  The executor builds
    its mesh from ``jax.devices()``, places real tables and asks the
    backend whether to interpret kernels; this steers those three to the
    described chips and to shapes.  Returns the compiled program."""
    from repro.kernels import ops
    from repro.relational import datagen
    from repro.relational.context import ExecutionContext
    from repro.relational.planner import executor as ex
    from repro.relational.planner import tpch
    from repro.relational.table import Table

    n = 4
    devices = np.array(topo.devices[:n])
    if num_pods > 1:
        mesh = Mesh(devices.reshape(num_pods, -1), ("pod", ex.SHUFFLE_AXIS))
    else:
        mesh = Mesh(devices, (ex.SHUFFLE_AXIS,))

    def place(table, num_shards, mesh, axes):
        cap = math.ceil(table.capacity / num_shards) * num_shards
        sharding = NamedSharding(mesh, P(axes))

        def shape(x):
            return jax.ShapeDtypeStruct((cap,), x.dtype, sharding=sharding)

        return {c: shape(v) for c, v in table.columns.items()}, shape(table.valid)

    monkeypatch.setattr(ex, "_mesh", lambda num_shards, num_pods=1: mesh)
    monkeypatch.setattr(ex, "_place", place)
    monkeypatch.setattr(ops, "_interpret", lambda: False)

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
    ctx = ExecutionContext(num_shards=n, num_pods=num_pods, pack_impl="pallas")
    run = ex.compile_plan(pq.plan(catalog, n, num_pods=num_pods), tables, ctx)
    return run._jfn.lower(*run._flat).compile()


def _device_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )


def test_q3_executor_compiles_on_four_chips(topo, monkeypatch):
    from repro.relational.planner import tpch

    compiled = _compile_sf4(topo, monkeypatch, tpch.q3())
    assert "tpu_custom_call" in compiled.as_text()
    assert 0 < _device_bytes(compiled) < HBM_BYTES, _device_bytes(compiled)


def test_q17_pod_program_fits_a_chip(topo, monkeypatch):
    """Q17 at SF 4 on the ``(pod, q)`` mesh: its joins probe 24M-row
    post-shuffle tables by the merge (no search loop), whose co-sorts are
    the program's largest operands, and the program still fits one chip's
    memory."""
    from repro.relational.planner import tpch

    compiled = _compile_sf4(topo, monkeypatch, tpch.q17(), num_pods=2)
    assert not re.search(r'while\(.*op_name="[^"]*/join_pk/', compiled.as_text())
    assert 0 < _device_bytes(compiled) < HBM_BYTES, _device_bytes(compiled)


@pytest.mark.parametrize("num_pods", [1, 2], ids=["flat", "pod"])
def test_q18_program_fits_a_chip(topo, monkeypatch, num_pods):
    """Q18 at SF 4 on the flat 4-way mesh and on the ``(pod, q)`` mesh: its
    two joins merge (no search loop) and the program fits one chip's
    memory."""
    from repro.relational.planner import tpch

    compiled = _compile_sf4(topo, monkeypatch, tpch.q18(), num_pods=num_pods)
    assert not re.search(r'while\(.*op_name="[^"]*/join_pk/', compiled.as_text())
    assert 0 < _device_bytes(compiled) < HBM_BYTES, _device_bytes(compiled)


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


# Q3's join of SF 1 orders into lineitem on one chip.
ORDERS_SF1 = 1_500_000


def _join_pk_hlo(one_chip, num_build: int, num_probe: int) -> str:
    from repro.relational import operators as ops

    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    return jax.jit(ops.join_pk).lower(
        shape(num_build, jnp.int32), shape(num_build, jnp.bool_),
        shape(num_probe, jnp.int32), shape(num_probe, jnp.bool_),
    ).compile().as_text()


@pytest.mark.parametrize("num_probe", [LINEITEM_SF1, 256])
def test_join_probe_compiles_without_a_loop(one_chip, num_probe):
    """SF 1 orders into lineitem (Q3), and into a small streamed morsel:
    the probe is two sorts and a scan, no loop of dependent gather passes."""
    assert "while(" not in _join_pk_hlo(one_chip, ORDERS_SF1, num_probe)
