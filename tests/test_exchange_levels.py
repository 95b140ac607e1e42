"""What each network level of an exchange carries, and where its ops sit on
the device trace.

* ``ExchangeEdge.crosspod_bytes`` / ``inpod_bytes`` (and the tracer's
  ``exchange.crosspod_bytes`` / ``exchange.inpod_bytes`` counters) equal a
  numpy count from the routing rule, ``hash(key) % N``, over seeded keys:
  on a ``(2, 2)`` pod mesh, and on a flat 4-way mesh, where nothing
  crosses pods.
* The two-level shuffle and broadcast put their pod-axis hop under a
  ``cross_pod`` scope and the in-pod hop under ``in_pod`` (the ops'
  ``op_name`` metadata); the flat shuffle enters neither, and its lowering
  is the same with every named scope switched off.

Each check runs in a subprocess with four CPU devices.
"""

import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
ROWS = 4096
N = 4


def _fib_hash(keys: np.ndarray) -> np.ndarray:
    """The exchange's key hash in numpy (uint32 arithmetic)."""
    x = keys.astype(np.uint64) & 0xFFFFFFFF
    x ^= x >> 16
    x = (x * 0x7FEB352D) & 0xFFFFFFFF
    x ^= x >> 15
    x = (x * 0x846CA68B) & 0xFFFFFFFF
    x ^= x >> 16
    return x


def check_counters(num_pods: int, seed: int = 3) -> None:
    from repro.obs.trace import Tracer
    from repro.relational.context import ExecutionContext
    from repro.relational.planner.logical import Aggregate, Filter, GroupBy, Scan, col, lit
    from repro.relational.planner.tpch import PlannedQuery, run_query
    from repro.relational.table import from_numpy

    rng = np.random.default_rng(seed)
    k = rng.integers(0, 50_000, ROWS).astype(np.int32)
    v = rng.integers(0, 1000, ROWS).astype(np.int32)
    f = Filter(Scan("t", ("k", "v")), col("v") < lit(700))
    g = GroupBy(f, key="k", aggs=(("s", col("v"), "sum"),))
    pq = PlannedQuery("levels", ("t",), Aggregate(g, (("total", col("s"), "sum"),)))
    tracer = Tracer()
    ctx = ExecutionContext(num_shards=N, num_pods=num_pods, trace=tracer)
    got = run_query(pq, {"t": from_numpy({"k": k, "v": v})}, ctx)
    assert float(got["total"]) == pytest.approx(float(v[v < 700].sum()), rel=1e-6)

    valid = v < 700
    src = np.arange(ROWS) % N  # the executor deals rows to devices round-robin
    dest = (_fib_hash(k) % N).astype(np.int64)
    n = N // num_pods
    cross = int(np.sum(valid & (dest // n != src // n)))
    inpod = int(np.sum(valid & (dest % n != src % n)))
    (qt,) = tracer.query_traces
    (edge,) = qt.edges
    assert edge.row_bytes == 8  # k and v, int32
    assert edge.hist == tuple(np.bincount(dest[valid], minlength=N))
    assert (edge.crosspod_bytes, edge.inpod_bytes) == (cross * 8, inpod * 8)
    assert tracer.counters["exchange.crosspod_bytes"] == cross * 8
    assert tracer.counters["exchange.inpod_bytes"] == inpod * 8
    if num_pods == 1:
        assert cross == 0
    else:
        assert cross > 0 and inpod > 0


def _lower(num_pods: int, exchange: str):
    """The executor mesh's global shuffle or broadcast, lowered."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    from repro.core.multiplexer import make_multiplexer
    from repro.relational.planner.executor import SHUFFLE_AXIS, _axes, _mesh

    mesh = _mesh(N, num_pods)
    mux = make_multiplexer(mesh, impl="round_robin", pack_impl="xla")
    axes = _axes(num_pods)

    def shuffle(k, r):
        return mux.hash_shuffle_global(k, r, SHUFFLE_AXIS, capacity=64)

    def broadcast(k, r):
        return mux.broadcast_global(r, SHUFFLE_AXIS).reshape(-1, 2)

    body, out_specs = {
        "shuffle": (shuffle, (P(axes), P(axes), P())),
        "broadcast": (broadcast, P()),
    }[exchange]
    fn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(axes), P(axes)), out_specs=out_specs,
        check_vma=False,
    ))
    k = jax.ShapeDtypeStruct((N * 64,), jnp.int32)
    r = jax.ShapeDtypeStruct((N * 64, 2), jnp.int32)
    return fn.lower(k, r)


def _collective_scopes(hlo: str) -> set[str]:
    """The level scope of every collective-permute in compiled HLO."""
    names = re.findall(r'collective-permute[^\n]*op_name="([^"]*)"', hlo)
    return {level for n in names for level in ("cross_pod", "in_pod") if f"/{level}/" in n}


def check_scopes() -> None:
    import contextlib

    import jax

    hlo = _lower(2, "shuffle").compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for level in ("cross_pod", "in_pod"):
        for phase in ("hash", "pack", "ship", "unpack"):
            assert any(f"/{level}/{phase}" in n for n in names), (level, phase)
    assert _collective_scopes(hlo) == {"cross_pod", "in_pod"}
    assert _collective_scopes(_lower(2, "broadcast").compile().as_text()) == {
        "cross_pod", "in_pod"}

    for exchange in ("shuffle", "broadcast"):
        flat = _lower(1, exchange)
        assert "cross_pod" not in flat.as_text(debug_info=True)
        assert "in_pod" not in flat.as_text(debug_info=True)
        scoped = flat.as_text()
        named_scope = jax.named_scope
        jax.named_scope = lambda name: contextlib.nullcontext()
        try:
            bare = _lower(1, exchange).as_text()
        finally:
            jax.named_scope = named_scope
        assert scoped == bare, exchange


def _in_subprocess(call: str) -> None:
    code = textwrap.dedent(f"""
        import sys
        sys.path[:0] = [{TESTS!r}, {os.path.join(os.path.dirname(TESTS), "src")!r}]
        import test_exchange_levels as t
        t.{call}
        print("DONE")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={N}")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0 and "DONE" in out.stdout, out.stdout[-2000:] + out.stderr[-4000:]


@pytest.mark.parametrize("num_pods", [1, 2])
def test_level_counters_match_numpy(num_pods):
    _in_subprocess(f"check_counters({num_pods})")


def test_two_level_hops_are_scoped_and_flat_shuffle_is_not():
    _in_subprocess("check_scopes()")
