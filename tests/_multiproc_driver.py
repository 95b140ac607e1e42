"""Pod-axis scenarios run as a REAL multi-process cluster (2 procs x 4 fake
CPU devices each, by default).

Invoked via the launcher:

    python -m repro.launch.cluster --processes 2 --local-devices 4 \
        tests/_multiproc_driver.py <scenario>

Every process runs the same scenario; collectives over the ``pod`` mesh axis
cross an actual process boundary (Gloo over localhost — the CI stand-in for
DCI).  Each scenario prints "PASS <name>" on success from every process; any
exception fails the run.  ``init_cluster()`` must run before anything
touches jax devices, so keep module-level imports jax-free.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.cluster import init_cluster  # noqa: E402

INFO = init_cluster()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.compat import fetch, shard_map  # noqa: E402
from repro.core import exchange  # noqa: E402
from repro.launch.mesh import make_pod_mesh, make_production_mesh  # noqa: E402
from repro.relational.context import ExecutionContext  # noqa: E402


def _pod_mesh():
    mesh = make_pod_mesh()
    assert mesh.axis_names == ("pod", "q"), mesh.axis_names
    return mesh


def scenario_hierarchical_psum():
    """RS-in-pod -> AR-cross-pod -> AG-in-pod equals a flat psum bit-exactly
    across the process boundary (int32 and exactly-representable float32)."""
    mesh = make_pod_mesh(axes=("pod", "data"))
    n = mesh.devices.size
    for dtype, hi in ((jnp.int32, 1 << 20), (jnp.float32, 1 << 12)):
        g = jax.random.randint(
            jax.random.PRNGKey(0), (n * 4, 3), 0, hi
        ).astype(dtype)

        def hier(g):
            return exchange.hierarchical_psum_tree({"g": g}, "data", "pod")["g"]

        def flat(g):
            return exchange.flat_psum_tree({"g": g}, ("pod", "data"))["g"]

        spec = P(("pod", "data"))
        a = jax.jit(shard_map(hier, mesh=mesh, in_specs=spec, out_specs=spec))(g)
        b = jax.jit(shard_map(flat, mesh=mesh, in_specs=spec, out_specs=spec))(g)
        np.testing.assert_array_equal(fetch(a), fetch(b), err_msg=str(dtype))
    print("PASS hierarchical_psum")


def scenario_exchange_over_dci_raises():
    """The hybrid plan rejects any fine-grained shuffle routed over the pod
    axis — at trace time, before a single byte crosses the slow network."""
    from repro.core.multiplexer import make_multiplexer

    mesh = _pod_mesh()
    mux = make_multiplexer(mesh)
    assert mux.plan.large_axes == ("pod",), mux.plan
    x = jnp.zeros((mesh.devices.shape[0], 4), jnp.int32)
    for attempt in (
        lambda: mux.all_to_all(x, "pod"),
        lambda: mux.hash_shuffle(x[:, 0], x, "pod", capacity=2),
        lambda: mux.shuffle_consume(
            x, "pod", lambda acc, c, s: acc, jnp.int32(0)
        ),
    ):
        try:
            attempt()
        except ValueError as e:
            assert "large-network axis" in str(e), e
        else:
            raise AssertionError("exchange over the DCI axis did not raise")
    print("PASS exchange_over_dci_raises")


def scenario_two_level_shuffle():
    """The two-level exchange (coarse cross-process hop + fine in-pod
    shuffle) loses no rows and lands every row on the device owning its
    global hash — across a real process boundary."""
    mesh = _pod_mesh()
    pods, n = mesh.devices.shape
    N = pods * n
    T = 64
    keys = jax.random.randint(jax.random.PRNGKey(3), (N * T,), 0, 10_000,
                              dtype=jnp.int32)
    rows = jnp.stack([keys, keys * 2 + 1], axis=1)

    def shuffle(k, r):
        out_rows, out_valid, dropped = exchange.hash_shuffle_two_level(
            k, r, "q", "pod", capacity=T
        )
        me = jax.lax.axis_index("pod") * n + jax.lax.axis_index("q")
        h = exchange.fibonacci_hash(
            out_rows[:, 0].astype(jnp.uint32)
        ) % jnp.uint32(N)
        ok = jnp.where(out_valid, h == me.astype(jnp.uint32), True).all()
        return out_valid.sum()[None], dropped, ok[None]

    spec = P(("pod", "q"))
    fn = shard_map(shuffle, mesh=mesh, in_specs=(spec, spec),
                   out_specs=(spec, P(), spec), check_vma=False)
    kept, dropped, ok = jax.jit(fn)(keys, rows)
    assert int(fetch(dropped)) == 0
    assert int(fetch(kept).sum()) == N * T
    assert bool(fetch(ok).all())
    print("PASS two_level_shuffle")


def scenario_production_mesh():
    """make_production_mesh derives the pod axis from the live process
    topology instead of the old hardcoded (2, 16, 16)."""
    mesh = make_production_mesh(multi_pod=True)
    assert mesh.axis_names == ("pod", "data", "model")
    assert mesh.devices.shape[0] == jax.process_count(), mesh.devices.shape
    assert mesh.devices.size == jax.device_count()
    print("PASS production_mesh")


def scenario_tpch_pod_mesh():
    """TPC-H Q3 and Q17 on the two-level mesh match the single-host numpy
    oracle — the full vertical slice: pod-aware planner, two-level
    exchanges, cross-pod combine."""
    from repro.relational import datagen, oracle
    from repro.relational.distributed import q3_distributed, q17_distributed

    mesh = _pod_mesh()
    pods, n = mesh.devices.shape
    tabs = datagen.gen_all(0.01)

    got17 = q17_distributed(
        tabs["lineitem"], tabs["part"],
        ExecutionContext(num_shards=pods * n, num_pods=pods),
    )
    np.testing.assert_allclose(
        float(got17), oracle.q17_oracle(tabs["lineitem"], tabs["part"]),
        rtol=1e-3,
    )

    got3 = q3_distributed(
        tabs["customer"], tabs["orders"], tabs["lineitem"],
        ExecutionContext(num_shards=pods * n, num_pods=pods),
    )
    want3 = oracle.q3_oracle(tabs["customer"], tabs["orders"], tabs["lineitem"])
    assert [int(k) for k in got3["o_orderkey"]] == \
        [int(k) for k in want3["o_orderkey"]]
    np.testing.assert_allclose(
        np.asarray(got3["revenue"], np.float64),
        np.asarray(want3["revenue"], np.float64), rtol=1e-3,
    )
    print("PASS tpch_pod_mesh")


def scenario_tuner_dci_aware():
    """tune_multiplexer on the live two-level mesh prices the DCI hop and
    picks a cross-pod strategy for the build side."""
    from repro.core.autotune import TableStats, exchange_makespan, tune_multiplexer

    mesh = _pod_mesh()
    pods, n = mesh.devices.shape
    stats = TableStats(rows=4096, row_bytes=16)
    cfg = tune_multiplexer(
        mesh, stats, broadcast_stats=TableStats(rows=128, row_bytes=12)
    )
    assert cfg.impl in ("xla", "round_robin", "one_factorization")
    assert cfg.cross_pod in ("broadcast", "reshard"), cfg
    # The two-level makespan must charge the coarse DCI hop: strictly more
    # than the same exchange priced single-pod.
    one = exchange_makespan(stats, n)
    two = exchange_makespan(stats, n, num_pods=pods)
    assert two > one, (one, two)
    # A big build side flips the choice to reshard.
    cfg_big = tune_multiplexer(
        mesh, stats, broadcast_stats=TableStats(rows=1 << 20, row_bytes=64)
    )
    assert cfg_big.cross_pod == "reshard", cfg_big
    print("PASS tuner_dci_aware")


def scenario_ep_dispatch_two_level():
    """MoE expert dispatch routed through the two-level fabric across a REAL
    process boundary is token-for-token identical to the flat all-to-all
    oracle (the same tokens shipped over a single joint mesh axis), and the
    flat route on the pod mesh is rejected at trace time — the exchange
    either takes the coarse-then-fine hops or does not run at all."""
    from repro.configs.base import ModelConfig
    from repro.core.multiplexer import make_multiplexer, use_multiplexer
    from repro.distributed.sharding import (
        MeshContext, default_rules, mesh_context,
    )
    from repro.models import moe

    cfg = ModelConfig(
        name="t", family="moe", num_layers=1, d_model=16, num_heads=2,
        num_kv_heads=2, d_ff=32, vocab_size=64, num_experts=8, top_k=2,
        moe_d_ff=32, moe_impl="ep_shardmap", capacity_factor=8.0,
        dtype="float32", param_dtype="float32",
    )
    # identical on every process (same seed) — the cluster-wide replicas
    params = moe.init_moe_layer(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model), jnp.float32)

    pod_mesh = make_pod_mesh(axes=("pod", "model"))
    pods, n = pod_mesh.devices.shape
    N = pods * n
    assert cfg.num_experts % N == 0 and x.shape[0] % N == 0, (cfg, N)

    flat_mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("model",))
    ctx_flat = MeshContext(mesh=flat_mesh, rules=default_rules(False),
                           data_axes=())
    ctx_pod = MeshContext(mesh=pod_mesh, rules=default_rules(True),
                          pod_axis="pod", data_axes=())

    with mesh_context(ctx_flat):
        want = fetch(moe.moe_ep(params, cfg, x))
    with mesh_context(ctx_pod):
        got = fetch(moe.moe_ep(params, cfg, x))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    # a single-level multiplexer must not silently flat-route over DCI
    mux_flat = make_multiplexer(flat_mesh)
    try:
        with mesh_context(ctx_pod), use_multiplexer(mux_flat):
            moe.moe_ep(params, cfg, x)
    except ValueError as e:
        assert "single-level multiplexer" in str(e), e
    else:
        raise AssertionError("flat mux on the pod mesh did not raise")
    print("PASS ep_dispatch_two_level")


def scenario_salted_pod_shuffle():
    """Salting works ACROSS the pod axis: Zipf(1.2) ``l_partkey`` Q17 on
    the 2x4 two-level mesh (the heavy key's sub-keys spread over all 8
    global shards, crossing the process boundary), measured max/fair-share
    strictly below the unsalted run, result equal to the numpy oracle."""
    from repro.relational import datagen, oracle
    from repro.relational import stats as rstats
    from repro.relational.planner import executor, tpch

    mesh = _pod_mesh()
    pods, n = mesh.devices.shape
    tabs = datagen.gen_all(0.01, zipf_partkey=1.2)
    pq = tpch.q17(brand=11, container=25)  # selects the heaviest part
    want = oracle.q17_oracle(tabs["lineitem"], tabs["part"], 11, 25)
    assert want > 0
    catalog = {t: tabs[t].capacity for t in pq.tables}
    stats = rstats.collect_stats({t: tabs[t] for t in pq.tables})

    plan = pq.plan(catalog, pods * n, num_pods=pods, stats=stats)
    assert "salted x" in plan.explain()
    run = executor.compile_plan(plan, tabs)
    raw, qt = run.collect(run.dispatch())
    got = pq.finalize(raw)
    np.testing.assert_allclose(float(got), want, rtol=1e-3)
    (edge,) = qt.edges
    assert edge.salted
    salted_over = float(edge.overload)
    plain_over = float(edge.plain_overload)
    assert plain_over > 2.0, plain_over
    assert salted_over < 1.3, salted_over

    run0 = executor.compile_plan(pq.plan(catalog, pods * n, num_pods=pods),
                                 tabs)
    raw0, qt0 = run0.collect(run0.dispatch())
    got0 = pq.finalize(raw0)
    np.testing.assert_allclose(float(got0), want, rtol=1e-3)
    (edge0,) = qt0.edges
    assert float(edge0.overload) == plain_over
    assert salted_over < float(edge0.overload)
    print("PASS salted_pod_shuffle")


def scenario_oocore_pod_stream():
    """Morsel-streamed Q17 ACROSS the process boundary: the chunked lineitem
    stream feeds the two-level (coarse cross-pod + fine in-pod) exchange one
    morsel at a time, result equal to the in-memory pod-mesh run."""
    from repro.relational import datagen
    from repro.relational.planner import tpch
    from repro.relational.planner.executor import execute_plan
    from repro.relational.planner.stream import compile_plan_streamed
    from repro.relational.source import MorselView, as_source

    mesh = _pod_mesh()
    pods, n = mesh.devices.shape
    tabs = datagen.gen_all(0.01)
    pq = tpch.q17()
    sources = {"lineitem": MorselView(tabs["lineitem"], morsel_rows=4096),
               "part": as_source(tabs["part"])}
    mat = {t: sources[t].materialize() for t in pq.tables}
    catalog = {t: sources[t].capacity for t in pq.tables}
    plan = pq.plan(catalog, pods * n, num_pods=pods)
    want = float(pq.finalize(execute_plan(plan, mat)))

    ctx = ExecutionContext(num_shards=pods * n, num_pods=pods)
    run = compile_plan_streamed(plan, sources, ctx)
    got = float(pq.finalize(run()))
    np.testing.assert_allclose(got, want, rtol=1e-3)
    assert run.stats["passes"] == 2, run.stats

    # spill is a single-level-mesh feature: over DCI it must refuse at
    # compile time, never drop rows at run time
    try:
        compile_plan_streamed(plan, sources, ctx.with_(spill=True))
    except NotImplementedError:
        pass
    else:
        raise AssertionError("spill on the pod mesh did not raise")
    print("PASS oocore_pod_stream")


def scenario_trace_merge():
    """One timeline for the whole cluster: each process traces its own Q17
    run and writes ``<dir>/q17-p<pid>.json``; after a cross-process
    barrier, process 0 merges them into a single Perfetto timeline whose
    events carry BOTH process tracks."""
    import json
    import shutil
    import tempfile

    from jax.experimental import multihost_utils

    from repro.obs.export import merge_trace_dir, write_trace_dir
    from repro.obs.trace import Tracer
    from repro.relational import datagen
    from repro.relational.planner import tpch

    # all processes of this cluster share a host; key the dir on the
    # coordinator address so concurrent clusters never collide
    tag = (INFO.coordinator or "solo").replace(":", "-").replace("/", "-")
    trace_dir = os.path.join(tempfile.gettempdir(), f"repro-trace-{tag}")
    if INFO.process_id == 0:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    multihost_utils.sync_global_devices("trace-dir-ready")

    mesh = _pod_mesh()
    pods, n = mesh.devices.shape
    tabs = datagen.gen_all(0.01)
    pq = tpch.q17()
    tracer = Tracer()  # pid resolves to jax.process_index()
    assert tracer.pid == INFO.process_id
    tpch.run_query(
        pq, {t: tabs[t] for t in pq.tables},
        ExecutionContext(num_shards=pods * n, num_pods=pods, trace=tracer),
    )
    path = write_trace_dir(tracer, trace_dir, basename="q17")
    assert path.endswith(f"q17-p{INFO.process_id}.json")
    multihost_utils.sync_global_devices("traces-written")

    if INFO.process_id == 0:
        merged = merge_trace_dir(
            trace_dir, basename="q17",
            out=os.path.join(trace_dir, "merged.json"),
        )
        pids = {e["pid"] for e in merged["traceEvents"]}
        assert pids == set(range(INFO.num_processes)), pids
        # every process contributed its execute span, its q17 QueryTrace
        # record with measured exchange edges, and its byte counters
        per_pid_names = {
            pid: {e["name"] for e in merged["traceEvents"]
                  if e["pid"] == pid and e["ph"] == "B"}
            for pid in pids
        }
        for pid, names in per_pid_names.items():
            assert "repro.execute" in names, (pid, names)
        records = merged["queryTraces"]
        assert [r["query"] for r in records] == ["q17"] * INFO.num_processes
        assert all(r["edges"] for r in records), records
        shipped = sum(e["measured_bytes"] for r in records for e in r["edges"])
        assert merged["counters"]["exchange.measured_bytes"] == shipped > 0
        with open(os.path.join(trace_dir, "merged.json")) as f:
            json.load(f)  # Perfetto-loadable JSON on disk
    multihost_utils.sync_global_devices("merge-checked")
    if INFO.process_id == 0:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print("PASS trace_merge")


SCENARIOS = {
    name.removeprefix("scenario_"): fn
    for name, fn in list(globals().items())
    if name.startswith("scenario_")
}

if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    names = list(SCENARIOS) if which == "all" else [which]
    for nm in names:
        SCENARIOS[nm]()
