"""Multi-device test scenarios, run as a subprocess with 8 fake devices.

Invoked as:  python tests/_multidev_driver.py <scenario> [...]
(the XLA fake-device flag must be set before jax initializes, which pytest
cannot do in-process — the assignment forbids setting it globally).
Each scenario prints "PASS <name>" on success; any exception fails the run.
"""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compat import shard_map  # noqa: E402
from repro.core import exchange  # noqa: E402
from repro.distributed.sharding import MeshContext, default_rules, mesh_context  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.relational.context import ExecutionContext as Ctx  # noqa: E402


def _mesh1d():
    return make_test_mesh((8,), ("x",))


def scenario_a2a_equiv():
    """scheduled/one_factorization all-to-all == XLA all-to-all."""
    mesh = _mesh1d()
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 4))
    outs = {}
    for impl in ("xla", "round_robin", "one_factorization"):
        fn = shard_map(
            lambda x, impl=impl: exchange.all_to_all(x, "x", impl=impl),
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        )
        outs[impl] = np.asarray(jax.jit(fn)(x))
    np.testing.assert_allclose(outs["round_robin"], outs["xla"])
    np.testing.assert_allclose(outs["one_factorization"], outs["xla"])
    print("PASS a2a_equiv")


def scenario_streaming_consume():
    """scheduled_all_to_all_consume folds the same chunks as the full shuffle."""
    mesh = _mesh1d()
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 4))

    def full(x):
        return exchange.all_to_all(x, "x", impl="xla").sum(axis=0)

    def stream(x):
        # each folded chunk is one device's row [4]; accumulate elementwise
        return exchange.scheduled_all_to_all_consume(
            x, "x", lambda acc, chunk, src: acc + chunk,
            jnp.zeros((4,), x.dtype),
        )

    a = jax.jit(shard_map(full, mesh=mesh, in_specs=P("x"), out_specs=P("x")))(x)
    b = jax.jit(shard_map(stream, mesh=mesh, in_specs=P("x"), out_specs=P("x")))(x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    print("PASS streaming_consume")


def scenario_hierarchical_psum():
    mesh = make_test_mesh((2, 4), ("pod", "data"))
    g = jax.random.normal(jax.random.PRNGKey(2), (16, 3))

    def hier(g):
        return exchange.hierarchical_psum_tree({"g": g}, "data", "pod")["g"]

    def flat(g):
        return exchange.flat_psum_tree({"g": g}, ("pod", "data"))["g"]

    a = jax.jit(shard_map(hier, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data"))))(g)
    b = jax.jit(shard_map(flat, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data"))))(g)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)
    print("PASS hierarchical_psum")


def scenario_hash_shuffle():
    """Every valid row lands on the shard owning its hash; none lost."""
    mesh = _mesh1d()
    keys = jax.random.randint(jax.random.PRNGKey(3), (256,), 0, 10_000)
    rows = jnp.stack([keys, keys * 2], axis=1)

    def shuffle(keys, rows):
        out_rows, out_valid, dropped = exchange.hash_shuffle(
            keys, rows, "x", capacity=64
        )
        me = jax.lax.axis_index("x")
        h = exchange.fibonacci_hash(out_rows[:, 0].astype(jnp.uint32)) % jnp.uint32(8)
        ok = jnp.where(out_valid, h == me.astype(jnp.uint32), True).all()
        return out_valid.sum()[None], dropped, ok[None]

    fn = shard_map(shuffle, mesh=mesh, in_specs=(P("x"), P("x")),
                       out_specs=(P("x"), P(), P("x")))
    kept, dropped, ok = jax.jit(fn)(keys, rows)
    assert int(dropped) == 0, int(dropped)
    assert int(jnp.asarray(kept).sum()) == 256
    assert bool(jnp.asarray(ok).all())
    print("PASS hash_shuffle")


def scenario_moe_ep():
    """EP shard_map MoE == dense oracle, both transports."""
    from repro.configs.base import ModelConfig
    from repro.models import moe as M

    cfg = ModelConfig(
        name="t", family="moe", num_layers=1, d_model=32, num_heads=4,
        num_kv_heads=4, d_ff=64, vocab_size=64, num_experts=16, top_k=4,
        moe_d_ff=48, capacity_factor=8.0, dtype="float32",
        moe_impl="ep_shardmap",
    )
    params = M.init_moe_layer(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 32))
    dense = M.moe_dense(params, cfg, x)
    mesh = make_test_mesh((2, 4), ("data", "model"))
    for impl in ("round_robin", "xla"):
        ctx = MeshContext(mesh=mesh, rules=default_rules(False),
                          exchange_axis="model", exchange_impl=impl)
        with mesh_context(ctx):
            ep = jax.jit(lambda p, x: M.moe_ep(p, cfg.scaled(exchange_impl=impl), x))(params, x)
        np.testing.assert_allclose(np.asarray(ep), np.asarray(dense), rtol=2e-4, atol=2e-5)
    print("PASS moe_ep")


def scenario_serve_continuous_ep():
    """Continuous-batching decode with EP dispatch over the multiplexer.

    An expert-parallel MoE model served by the continuous engine on a
    (2, 4) mesh: the engine auto-tunes a CommMultiplexer for the
    decode-shaped expert messages (tiny -> unchunked scheduled transport)
    and the MoE layer ships its capacity buffers through it.  Greedy
    outputs must be bit-identical to the STATIC engine on the same mesh
    (same numerics family, same batch shapes), and a mixed-length workload
    must finish with no slot leak and fewer slot-steps.
    """
    from repro.configs import get_smoke_config
    from repro.models import registry as R
    from repro.serve import (
        ContinuousEngine, Request, ServeEngine, generate_bucketed,
    )

    cfg = get_smoke_config("olmoe-1b-7b").scaled(
        moe_impl="ep_shardmap", capacity_factor=8.0
    )
    api = R.build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    mesh = make_test_mesh((2, 4), ("data", "model"))
    ctx = MeshContext(mesh=mesh, rules=default_rules(False),
                      exchange_axis="model", exchange_impl="round_robin")
    rng = np.random.default_rng(0)
    B, cap = 4, 48

    with mesh_context(ctx):
        same = [rng.integers(0, cfg.vocab_size, 8, dtype=np.int32)
                for _ in range(B)]
        reqs_s = [Request(prompt=p.copy(), max_new_tokens=5) for p in same]
        reqs_c = [Request(prompt=p.copy(), max_new_tokens=5) for p in same]
        se = ServeEngine(api, batch_size=B, capacity=cap)
        se.generate(params, reqs_s)
        ce = ContinuousEngine(api, batch_size=B, capacity=cap)
        assert ce.mux is not None, "EP engine must build a decode multiplexer"
        # decode-shaped stats: tiny messages -> no chunking
        assert ce.mux.pipeline_chunks == 1 and ce.mux.transport_chunks == 1, ce.mux
        ce.serve(params, reqs_c)
        for a, b in zip(reqs_s, reqs_c):
            assert a.out_tokens == b.out_tokens, (a.out_tokens, b.out_tokens)

        mixed = [
            Request(prompt=rng.integers(0, cfg.vocab_size, pl, dtype=np.int32),
                    max_new_tokens=int(mn))
            for pl, mn in zip([8, 16] * 4, rng.integers(2, 10, 8))
        ]
        mixed_c = [Request(prompt=r.prompt.copy(), max_new_tokens=r.max_new_tokens)
                   for r in mixed]
        se2 = ServeEngine(api, batch_size=B, capacity=cap)
        generate_bucketed(se2, params, mixed)
        ce2 = ContinuousEngine(api, batch_size=B, capacity=cap)
        ce2.serve(params, mixed_c)
        ce2.alloc.check()
        assert all(r.done for r in mixed_c)
        assert ce2.stats["slot_steps"] < se2.stats["slot_steps"], (
            ce2.stats, se2.stats
        )
    print("PASS serve_continuous_ep")


def scenario_serve_continuous_ep_pods():
    """Continuous vs static greedy decode on a num_pods=2 mesh: the EP
    dispatch crosses the pod boundary through the two-level fabric (the
    engine's auto-tuned multiplexer carries a two-level plan), and the
    continuous engine's greedy tokens are bit-identical to the static
    engine's — the same guarantee as the flat-mesh case, now with the
    exchange routed coarse-then-fine.
    """
    from repro.configs import get_smoke_config
    from repro.models import registry as R
    from repro.serve import ContinuousEngine, Request, ServeEngine

    cfg = get_smoke_config("olmoe-1b-7b").scaled(
        moe_impl="ep_shardmap", capacity_factor=8.0
    )
    api = R.build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    # (pod, data, model): 2 pods x 4-way exchange = 8 joint EP units.
    # batch_size=8 keeps decode T divisible by the unit count — smaller
    # batches would silently fall back to the dense path and test nothing.
    mesh = make_test_mesh((2, 1, 4), ("pod", "data", "model"))
    ctx = MeshContext(mesh=mesh, rules=default_rules(True),
                      exchange_axis="model", pod_axis="pod",
                      exchange_impl="round_robin")
    rng = np.random.default_rng(0)
    B, cap = 8, 48

    with mesh_context(ctx):
        same = [rng.integers(0, cfg.vocab_size, 8, dtype=np.int32)
                for _ in range(B)]
        reqs_s = [Request(prompt=p.copy(), max_new_tokens=5) for p in same]
        reqs_c = [Request(prompt=p.copy(), max_new_tokens=5) for p in same]
        se = ServeEngine(api, batch_size=B, capacity=cap)
        se.generate(params, reqs_s)
        ce = ContinuousEngine(api, batch_size=B, capacity=cap)
        assert ce.mux is not None, "EP engine must build a decode multiplexer"
        assert ce.mux.plan.pod_axis == "pod" and ce.mux.plan.num_pods == 2, (
            "the decode multiplexer must carry the two-level plan", ce.mux.plan
        )
        ce.serve(params, reqs_c)
        ce.alloc.check()
        for a, b in zip(reqs_s, reqs_c):
            assert a.out_tokens == b.out_tokens, (a.out_tokens, b.out_tokens)
    print("PASS serve_continuous_ep_pods")


def scenario_sharded_train_equiv():
    """Sharded train step == single-device train step (same numbers)."""
    from repro.configs import get_smoke_config
    from repro.models import registry as R
    from repro.train import AdamWConfig, make_train_step
    from repro.train.step import TrainState, state_shardings

    cfg = get_smoke_config("qwen2.5-3b")
    api = R.build(cfg)
    key = jax.random.PRNGKey(0)
    state = TrainState.create(api, key)
    batch = {
        "tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (8, 16), 0, cfg.vocab_size),
    }
    step = make_train_step(api, AdamWConfig(lr=1e-3))
    _, m_ref = jax.jit(step)(state, batch)

    mesh = make_test_mesh((4, 2), ("data", "model"))
    ctx = MeshContext(mesh=mesh, rules=default_rules(False),
                      exchange_axis="model", exchange_impl="round_robin")
    with mesh_context(ctx):
        sh = state_shardings(api, ctx)
        state_s = jax.device_put(state, sh)
        _, m_shard = jax.jit(step)(state_s, batch)
    np.testing.assert_allclose(
        float(m_ref["loss"]), float(m_shard["loss"]), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(m_ref["grad_norm"]), float(m_shard["grad_norm"]), rtol=1e-4
    )
    print("PASS sharded_train_equiv")


def scenario_ckpt_elastic():
    """Save sharded on a (4,2) mesh, restore onto (2,4): elastic restart."""
    import tempfile
    from repro.checkpoint import save_checkpoint, restore_checkpoint
    from repro.distributed.sharding import logical_sharding

    mesh_a = make_test_mesh((4, 2), ("data", "model"))
    mesh_b = make_test_mesh((2, 4), ("data", "model"))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    ctx_a = MeshContext(mesh=mesh_a, rules=default_rules(False))
    ctx_b = MeshContext(mesh=mesh_b, rules=default_rules(False))
    xa = jax.device_put(x, logical_sharding(x.shape, "batch", "d_ff", ctx=ctx_a))
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 3, {"w": xa})
        shard_b = {"w": logical_sharding(x.shape, "batch", "d_ff", ctx=ctx_b)}
        restored = restore_checkpoint(d, None, {"w": jax.eval_shape(lambda: x)}, shard_b)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(x))
    assert restored["w"].sharding.spec == shard_b["w"].spec
    print("PASS ckpt_elastic")


def scenario_distributed_q17():
    """Paper's Fig 6 query distributed over 8 shards == numpy oracle."""
    from repro.relational import datagen, oracle
    from repro.relational.distributed import q17_distributed

    tabs = datagen.gen_all(0.01)
    got = q17_distributed(tabs["lineitem"], tabs["part"], Ctx(num_shards=8))
    want = oracle.q17_oracle(tabs["lineitem"], tabs["part"])
    np.testing.assert_allclose(float(got), want, rtol=1e-3)
    print("PASS distributed_q17")


def scenario_distributed_q14_q19():
    """Q14/Q19 over the partition+broadcast plan == numpy oracle."""
    from repro.relational import datagen, oracle
    from repro.relational.distributed import q14_distributed, q19_distributed

    tabs = datagen.gen_all(0.01)
    li, part = tabs["lineitem"], tabs["part"]
    got14 = float(q14_distributed(li, part, Ctx(num_shards=8)))
    np.testing.assert_allclose(got14, oracle.q14_oracle(li, part), rtol=1e-3)
    got19 = float(q19_distributed(li, part, Ctx(num_shards=8)))
    np.testing.assert_allclose(got19, oracle.q19_oracle(li, part), rtol=1e-3)
    print("PASS distributed_q14_q19")


def scenario_decode_sharded_equiv():
    """Sharded decode step == single-device decode step."""
    from repro.configs import get_smoke_config
    from repro.models import registry as R

    cfg = get_smoke_config("deepseek-67b")
    api = R.build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    cache = api.init_cache(8, 32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 1), 0, cfg.vocab_size)
    logits_ref, _ = jax.jit(api.decode_step)(params, toks, cache, jnp.int32(5))

    mesh = make_test_mesh((2, 4), ("data", "model"))
    ctx = MeshContext(mesh=mesh, rules=default_rules(False))
    with mesh_context(ctx):
        logits_s, _ = jax.jit(api.decode_step)(params, toks, cache, jnp.int32(5))
    np.testing.assert_allclose(
        np.asarray(logits_ref), np.asarray(logits_s), rtol=2e-4, atol=2e-4
    )
    print("PASS decode_sharded_equiv")


def scenario_hash_shuffle_equiv():
    """hash_shuffle delivers the same rows per device across every transport
    (xla / round_robin / one_factorization), pack impl (xla / pallas) and
    pipeline chunking (1 / 4), on uniform and heavily skewed keys."""
    mesh = _mesh1d()
    rng = np.random.default_rng(0)
    uniform = rng.integers(0, 10_000, 256)
    skewed = np.where(rng.random(256) < 0.8, 7, rng.integers(0, 10_000, 256))
    for name, keys_np in (("uniform", uniform), ("skewed", skewed)):
        keys = jnp.asarray(keys_np, jnp.int32)
        rows = jnp.stack([keys, keys * 2 + 1], axis=1)
        baseline = None
        configs = [
            (impl, pack_impl, chunks, 1)
            for impl in ("xla", "round_robin", "one_factorization")
            for pack_impl in ("xla", "pallas")
            for chunks in (1, 4)
        ] + [("round_robin", "pallas", 4, 2)]  # + split-phase transport
        for impl, pack_impl, chunks, transport in configs:
            def shuffle(keys, rows, impl=impl, pack=pack_impl, ch=chunks,
                        tc=transport):
                return exchange.hash_shuffle(
                    keys, rows, "x", capacity=32, impl=impl,
                    pack_impl=pack, num_chunks=ch, transport_chunks=tc,
                )
            fn = shard_map(
                shuffle, mesh=mesh, in_specs=(P("x"), P("x")),
                out_specs=(P("x"), P("x"), P()),
                check_vma=False,  # no replication rule for pallas_call
            )
            r, v, d = jax.jit(fn)(keys, rows)
            assert int(d) == 0, (name, impl, pack_impl, chunks, int(d))
            r, v = np.asarray(r), np.asarray(v)
            per_dev = []
            for j in range(8):
                rows_j = r[j * 256:(j + 1) * 256][v[j * 256:(j + 1) * 256]]
                order = np.lexsort(rows_j.T)
                per_dev.append(rows_j[order])
            if baseline is None:
                baseline = per_dev
                assert sum(len(b) for b in baseline) == 256
            else:
                for j in range(8):
                    np.testing.assert_array_equal(
                        per_dev[j], baseline[j],
                        err_msg=f"{name}/{impl}/{pack_impl}/c{chunks}/dev{j}",
                    )
    print("PASS hash_shuffle_equiv")


def scenario_consume_equiv():
    """Streaming consume folds the same (chunk, src) pairs under every
    schedule as the materialize-then-fold xla baseline."""
    mesh = _mesh1d()
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 4))

    def fold(acc, chunk, src):
        return acc + chunk * (jnp.float32(src) + 1.0)  # src-weighted: order-free

    def baseline(x):
        y = exchange.all_to_all(x, "x", impl="xla")
        acc = jnp.zeros((4,), x.dtype)
        for j in range(8):
            acc = fold(acc, y[j], j)
        return acc

    want = np.asarray(jax.jit(
        shard_map(baseline, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    )(x))
    for schedule in ("shift", "one_factorization"):
        def stream(x, schedule=schedule):
            return exchange.scheduled_all_to_all_consume(
                x, "x", fold, jnp.zeros((4,), x.dtype), schedule=schedule
            )
        got = np.asarray(jax.jit(
            shard_map(stream, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
        )(x))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6, err_msg=schedule)
    print("PASS consume_equiv")


def scenario_mux_schedule_fallback():
    """make_multiplexer downgrades one_factorization on odd-sized axes to the
    shift schedule instead of letting an invalid config reach trace time."""
    import warnings
    from repro.core.multiplexer import make_multiplexer

    mesh3 = jax.sharding.Mesh(np.asarray(jax.devices()[:3]), ("x",))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        mux = make_multiplexer(mesh3, impl="one_factorization")
    assert mux.impl == "round_robin", mux.impl
    assert any("one_factorization" in str(x.message) for x in w), [str(x.message) for x in w]

    x = jax.random.normal(jax.random.PRNGKey(5), (9, 4))
    got = np.asarray(jax.jit(shard_map(
        lambda x: mux.all_to_all(x, "x"), mesh=mesh3, in_specs=P("x"), out_specs=P("x")
    ))(x))
    want = np.asarray(jax.jit(shard_map(
        lambda x: exchange.all_to_all(x, "x", impl="xla"),
        mesh=mesh3, in_specs=P("x"), out_specs=P("x"),
    ))(x))
    np.testing.assert_allclose(got, want)

    mesh4 = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("x",))
    mux4 = make_multiplexer(mesh4, impl="one_factorization")
    assert mux4.impl == "one_factorization", mux4.impl
    print("PASS mux_schedule_fallback")


def scenario_autotune_mux():
    """An auto-tuned multiplexer (knobs from the topology cost model, no
    hand-set values) shuffles identically to the monolithic-XLA baseline,
    and empirical refinement picks a measured winner on the live mesh."""
    from repro.core.autotune import TableStats, tune_multiplexer
    from repro.core.multiplexer import make_multiplexer

    mesh = _mesh1d()
    rows_per_dev = 64
    stats = TableStats(rows=rows_per_dev, row_bytes=8)
    mux = make_multiplexer(mesh, auto=True, table_stats=stats)
    assert mux.pipeline_chunks >= 1 and mux.transport_chunks >= 1
    assert mux.impl in ("xla", "round_robin", "one_factorization")

    keys = jax.random.randint(jax.random.PRNGKey(7), (8 * rows_per_dev,), 0, 10_000)
    rows = jnp.stack([keys, keys * 3 + 1], axis=1).astype(jnp.int32)

    def shuffle(mux):
        def body(k, r):
            return mux.hash_shuffle(k, r, "x", capacity=rows_per_dev)
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("x"), P("x")),
            out_specs=(P("x"), P("x"), P()), check_vma=False,
        ))

    r_auto, v_auto, d_auto = shuffle(mux)(keys.astype(jnp.int32), rows)
    base = make_multiplexer(mesh, impl="xla", pack_impl="xla")
    r_ref, v_ref, d_ref = shuffle(base)(keys.astype(jnp.int32), rows)
    assert int(d_auto) == 0 and int(d_ref) == 0
    for j in range(8):
        sl = slice(j * 8 * rows_per_dev, (j + 1) * 8 * rows_per_dev)
        got = np.asarray(r_auto)[sl][np.asarray(v_auto)[sl]]
        want = np.asarray(r_ref)[sl][np.asarray(v_ref)[sl]]
        np.testing.assert_array_equal(
            got[np.lexsort(got.T)], want[np.lexsort(want.T)], err_msg=f"dev{j}"
        )

    refined = tune_multiplexer(mesh, stats, refine=True, refine_top_k=2)
    assert refined.measured_s is not None and refined.measured_s > 0
    print("PASS autotune_mux")


def scenario_two_level_shuffle():
    """hash_shuffle_two_level on a (2, 4) pod mesh delivers each row to the
    same device as a flat hash % 8 shuffle over a joint 8-way axis — for
    every transport/pack combination, including skewed keys."""
    pod_mesh = make_test_mesh((2, 4), ("pod", "q"))
    flat_mesh = _mesh1d()
    rng = np.random.default_rng(42)
    for name, keys_np in (
        ("uniform", rng.integers(0, 10_000, 256)),
        ("skewed", np.where(rng.random(256) < 0.8, 7,
                            rng.integers(0, 10_000, 256))),
    ):
        keys = jnp.asarray(keys_np, jnp.int32)
        rows = jnp.stack([keys, keys * 5 + 3], axis=1)

        def flat(k, r):
            return exchange.hash_shuffle(k, r, "x", capacity=32)

        fr, fv, fd = jax.jit(shard_map(
            flat, mesh=flat_mesh, in_specs=(P("x"), P("x")),
            out_specs=(P("x"), P("x"), P()),
        ))(keys, rows)
        assert int(fd) == 0

        def want_rows(j):
            r, v = np.asarray(fr), np.asarray(fv)
            rows_j = r[j * 256:(j + 1) * 256][v[j * 256:(j + 1) * 256]]
            return rows_j[np.lexsort(rows_j.T)]

        for impl, pack_impl, chunks in (
            ("xla", "xla", 1), ("round_robin", "xla", 1),
            ("round_robin", "pallas", 4), ("one_factorization", "xla", 2),
        ):
            def two(k, r, impl=impl, pack=pack_impl, ch=chunks):
                return exchange.hash_shuffle_two_level(
                    k, r, "q", "pod", capacity=32, impl=impl,
                    pack_impl=pack, num_chunks=ch,
                )
            tr, tv, td = jax.jit(shard_map(
                two, mesh=pod_mesh, in_specs=(P(("pod", "q")), P(("pod", "q"))),
                out_specs=(P(("pod", "q")), P(("pod", "q")), P()),
                check_vma=False,
            ))(keys, rows)
            assert int(td) == 0, (name, impl, pack_impl, chunks, int(td))
            tr, tv = np.asarray(tr), np.asarray(tv)
            # device (pod p, inner i) = flat device p*4 + i; each holds
            # [4 * 2 * 32] = 256 output slots
            for j in range(8):
                rows_j = tr[j * 256:(j + 1) * 256][tv[j * 256:(j + 1) * 256]]
                got = rows_j[np.lexsort(rows_j.T)]
                np.testing.assert_array_equal(
                    got, want_rows(j),
                    err_msg=f"{name}/{impl}/{pack_impl}/c{chunks}/dev{j}",
                )

    # float32 rows with int32 keys: hop 1 cannot fold the keys into the row
    # matrix (dtype mismatch) and takes the separate-buffers path
    keys = jnp.asarray(rng.integers(0, 10_000, 256), jnp.int32)
    frows = jnp.stack([keys * 1.5, keys * 0.25], axis=1).astype(jnp.float32)
    fr, fv, fd = jax.jit(shard_map(
        lambda k, r: exchange.hash_shuffle(k, r, "x", capacity=32),
        mesh=flat_mesh, in_specs=(P("x"), P("x")),
        out_specs=(P("x"), P("x"), P()),
    ))(keys, frows)
    tr, tv, td = jax.jit(shard_map(
        lambda k, r: exchange.hash_shuffle_two_level(
            k, r, "q", "pod", capacity=32
        ),
        mesh=pod_mesh, in_specs=(P(("pod", "q")), P(("pod", "q"))),
        out_specs=(P(("pod", "q")), P(("pod", "q")), P()), check_vma=False,
    ))(keys, frows)
    assert int(fd) == 0 and int(td) == 0
    fr, fv, tr, tv = map(np.asarray, (fr, fv, tr, tv))
    for j in range(8):
        a = fr[j * 256:(j + 1) * 256][fv[j * 256:(j + 1) * 256]]
        b = tr[j * 256:(j + 1) * 256][tv[j * 256:(j + 1) * 256]]
        np.testing.assert_array_equal(
            a[np.lexsort(a.T)], b[np.lexsort(b.T)], err_msg=f"float/dev{j}"
        )
    print("PASS two_level_shuffle")


def scenario_tpch_pod_mesh_1proc():
    """TPC-H on a two-level (2 pods x 4) mesh — single process, fake DCI:
    Q17 matches the oracle under BOTH cross-pod build-side strategies, and
    Q3's two chained two-level exchanges + cross-pod top-k combine match the
    single-pod run exactly."""
    from repro.relational import datagen, oracle
    from repro.relational.distributed import q3_distributed, q17_distributed

    tabs = datagen.gen_all(0.01)
    li, pt = tabs["lineitem"], tabs["part"]
    want17 = oracle.q17_oracle(li, pt)
    for cross_pod in ("broadcast", "reshard"):
        got = q17_distributed(
            li, pt, Ctx(num_shards=8, num_pods=2, impl="round_robin",
                        pack_impl="pallas", cross_pod=cross_pod),
        )
        np.testing.assert_allclose(float(got), want17, rtol=1e-3,
                                   err_msg=cross_pod)

    flat = q3_distributed(tabs["customer"], tabs["orders"], li, Ctx(num_shards=8))
    pod = q3_distributed(tabs["customer"], tabs["orders"], li,
                         Ctx(num_shards=8, num_pods=2))
    for k in flat:
        np.testing.assert_array_equal(np.asarray(flat[k]), np.asarray(pod[k]),
                                      err_msg=k)
    print("PASS tpch_pod_mesh_1proc")


def scenario_distributed_q1_q6():
    """Q1/Q6 (the no-network queries, paper Fig 11) over 8 shards match the
    numpy oracle, on both the flat mesh and a (2 pods x 4) two-level mesh —
    and the pod run equals the flat run exactly."""
    from repro.relational import datagen, oracle
    from repro.relational.distributed import q1_distributed, q6_distributed

    tabs = datagen.gen_all(0.01)
    li = tabs["lineitem"]
    want1 = oracle.q1_oracle(li)
    want6 = oracle.q6_oracle(li)
    flat1 = q1_distributed(li, Ctx(num_shards=8))
    for k in want1:
        np.testing.assert_allclose(np.asarray(flat1[k]), want1[k], rtol=1e-4,
                                   err_msg=k)
    pod1 = q1_distributed(li, Ctx(num_shards=8, num_pods=2))
    for k in flat1:
        np.testing.assert_allclose(np.asarray(flat1[k]), np.asarray(pod1[k]),
                                   rtol=1e-6, err_msg=f"pod/{k}")
    flat6 = float(q6_distributed(li, Ctx(num_shards=8)))
    np.testing.assert_allclose(flat6, want6, rtol=1e-4)
    pod6 = float(q6_distributed(li, Ctx(num_shards=8, num_pods=2)))
    np.testing.assert_allclose(pod6, flat6, rtol=1e-6)
    print("PASS distributed_q1_q6")


def scenario_planner_new_queries():
    """The plan-only queries (Q4/Q12/Q18 — no hand-written distributed
    version exists) over 8 shards match the numpy oracle, and Q18 on a
    (2 pods x 4) two-level mesh equals the flat run exactly."""
    from repro.relational import datagen, oracle
    from repro.relational.distributed import (
        q4_distributed, q12_distributed, q18_distributed,
    )

    tabs = datagen.gen_all(0.01)
    li, od, cu = tabs["lineitem"], tabs["orders"], tabs["customer"]

    got4 = q4_distributed(li, od, Ctx(num_shards=8))
    want4 = oracle.q4_oracle(li, od)
    assert want4.sum() > 0
    np.testing.assert_allclose(np.asarray(got4["order_count"]), want4)

    got12 = q12_distributed(li, od, Ctx(num_shards=8))
    want12 = oracle.q12_oracle(li, od)
    np.testing.assert_allclose(got12["high_line_count"],
                               want12["high_line_count"])
    np.testing.assert_allclose(got12["low_line_count"],
                               want12["low_line_count"])

    got18 = q18_distributed(li, od, cu, Ctx(num_shards=8))
    want18 = oracle.q18_oracle(li, od, cu)
    assert len(want18["o_orderkey"]) > 0
    got_map = {int(k): (int(tp), float(sq)) for k, tp, sq in zip(
        got18["o_orderkey"], got18["o_totalprice"], got18["sum_qty"])}
    want_map = {int(k): (int(tp), float(sq)) for k, tp, sq in zip(
        want18["o_orderkey"], want18["o_totalprice"], want18["sum_qty"])}
    assert got_map == want_map, (got_map, want_map)

    pod18 = q18_distributed(li, od, cu, Ctx(num_shards=8, num_pods=2))
    for k in got18:
        np.testing.assert_array_equal(
            np.asarray(got18[k]), np.asarray(pod18[k]), err_msg=f"pod/{k}"
        )
    print("PASS planner_new_queries")


def scenario_tpch_pack_equiv():
    """Scheduled transport + Pallas fused pack matches the monolithic-XLA
    baseline bit-exactly on the TPC-H join queries (Q17 and Q3)."""
    from repro.relational import datagen
    from repro.relational.distributed import q17_distributed, q3_distributed

    tabs = datagen.gen_all(0.01)
    a17 = q17_distributed(tabs["lineitem"], tabs["part"],
                          Ctx(num_shards=8, impl="xla", pack_impl="xla"))
    b17 = q17_distributed(tabs["lineitem"], tabs["part"],
                          Ctx(num_shards=8, impl="round_robin",
                              pack_impl="pallas"))
    np.testing.assert_array_equal(np.asarray(a17), np.asarray(b17))

    a3 = q3_distributed(tabs["customer"], tabs["orders"], tabs["lineitem"],
                        Ctx(num_shards=8, impl="xla", pack_impl="xla"))
    b3 = q3_distributed(tabs["customer"], tabs["orders"], tabs["lineitem"],
                        Ctx(num_shards=8, impl="round_robin",
                            pack_impl="pallas"))
    for k in a3:
        np.testing.assert_array_equal(np.asarray(a3[k]), np.asarray(b3[k]))
    print("PASS tpch_pack_equiv")


def scenario_skewed_q17():
    """The adaptive-optimizer acceptance scenario (paper §3.1): Zipf(1.2)
    ``l_partkey`` over 8 shards.  Stats flip Q17's shared lineitem shuffle
    to the salted repartitioning; the executor measures per-shard load at
    the exchange and reports it.  Asserts: salted matches the oracle with
    zero drops, the measured max/fair-share of the salted route stays
    strictly below the unsalted one (< 1.3 vs > 2), and uniform data
    through the SAME salted plan keeps the plain route (runtime gate)."""
    from repro.relational import datagen, oracle
    from repro.relational import stats as rstats
    from repro.relational.planner import executor, tpch

    tabs = datagen.gen_all(0.01, zipf_partkey=1.2)
    # brand/container of partkey 0, the heaviest Zipf key (~22% of rows):
    # the semi-join keeps it, so the shuffle actually sees the skew
    pq = tpch.q17(brand=11, container=25)
    want = oracle.q17_oracle(tabs["lineitem"], tabs["part"], 11, 25)
    assert want > 0
    catalog = {t: tabs[t].capacity for t in pq.tables}
    stats = rstats.collect_stats({t: tabs[t] for t in pq.tables})

    salted_plan = pq.plan(catalog, 8, stats=stats)
    assert "salted x" in salted_plan.explain()
    run = executor.compile_plan(salted_plan, tabs)
    raw, qt = run.collect(run.dispatch())  # collect raises on dropped rows
    got = pq.finalize(raw)
    np.testing.assert_allclose(float(got), want, rtol=1e-3)
    (edge,) = qt.edges
    assert edge.salted
    plain_over = float(edge.plain_overload)
    salted_over = float(edge.overload)
    assert plain_over > 2.0, plain_over
    assert salted_over < 1.3, salted_over
    assert salted_over < plain_over

    # the static plan routes plain and eats the full overload
    run0 = executor.compile_plan(pq.plan(catalog, 8), tabs)
    raw0, qt0 = run0.collect(run0.dispatch())
    got0 = pq.finalize(raw0)
    np.testing.assert_allclose(float(got0), want, rtol=1e-3)
    (edge0,) = qt0.edges
    assert float(edge0.overload) == plain_over

    # runtime gate: a salted PLAN on balanced data keeps the plain route.
    # Q17's shuffle sits behind the semi-join (2 surviving keys are
    # legitimately imbalanced even uniform), so the gate is shown on
    # Q18's scan-fed group-by exchange instead: plan from Zipf orderkeys,
    # execute on uniform ones.
    pq18 = tpch.q18()
    z18 = datagen.gen_all(0.01, zipf_orderkey=1.5)
    cat18 = {t: z18[t].capacity for t in pq18.tables}
    plan18 = pq18.plan(
        cat18, 8, stats=rstats.collect_stats({t: z18[t] for t in pq18.tables})
    )
    assert "salted x" in plan18.explain()
    uni = datagen.gen_all(0.01)
    run_u = executor.compile_plan(plan18, uni)
    raw_u, qt_u = run_u.collect(run_u.dispatch())
    got_u = pq18.finalize(raw_u)
    want_u = oracle.q18_oracle(uni["lineitem"], uni["orders"], uni["customer"])
    for k in want_u:
        np.testing.assert_allclose(
            np.asarray(got_u[k]), np.asarray(want_u[k]), rtol=1e-3
        )
    edge_u = next(e for e in qt_u.edges if "l_orderkey" in e.key)
    assert not edge_u.salted
    assert float(edge_u.plain_overload) < 1.5
    print("PASS skewed_q17")


def scenario_qserve_cached():
    """The query-serving engine on the real 8-device mesh: all nine TPC-H
    templates served cold then warm through one QueryServeEngine.  The
    warm pass makes ZERO ``plan_physical`` calls (plan cache) and zero
    retraces (executor memo), returns results bit-identical to the cold
    pass, and spot-checked queries are bit-identical to a solo
    ``compile_plan`` run sharing the engine's multiplexer.  The slot
    invariant holds after every drain."""
    from repro.relational import datagen
    from repro.relational.planner import executor, tpch
    from repro.relational.planner.physical import plan_physical
    from repro.relational.planner.plan_cache import PlanCache
    from repro.serve import QueryRequest, QueryServeEngine

    tabs = datagen.gen_all(0.01)
    templates = [make() for make in tpch.ALL_QUERIES.values()]
    names = sorted({t for pq in templates for t in pq.tables})
    tables = {name: tabs[name] for name in names}
    engine = QueryServeEngine(
        tables, Ctx(num_shards=8), num_slots=3, cache=PlanCache(),
        templates=templates,
    )
    cold = engine.serve([QueryRequest("t", pq) for pq in templates])
    engine.alloc.check()
    assert engine.alloc.num_free == 3 and not engine.alloc.live

    before = plan_physical.calls
    warm = engine.serve([QueryRequest("t", pq) for pq in templates])
    assert plan_physical.calls == before, "warm path replanned"
    assert all(r.plan_cache_hit and r.executor_cache_hit for r in warm)
    engine.alloc.check()

    def eq(a, b):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        return len(la) == len(lb) and all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(la, lb)
        )

    by_name = {r.query.name: r.result for r in cold}
    for r in warm:
        assert eq(r.result, by_name[r.query.name]), r.query.name
    # solo run, same mux: the engine changes scheduling, never bytes
    for qname in ("q3", "q17"):
        pq = next(p for p in templates if p.name == qname)
        plan = pq.plan({t: tables[t].capacity for t in pq.tables}, 8)
        run = executor.compile_plan(plan, tables, mux=engine._mux)
        assert eq(pq.finalize(run()), by_name[qname]), qname
    print("PASS qserve_cached")


def scenario_exchange_report():
    """Exchange reports are comparable across plan lifecycles: a cold Q3
    run, a replanned run, and a run from an UNPICKLED cached plan emit
    identical report keys (``shuffle[col]#ordinal``) AND identical values
    on the 8-device mesh — the regression that display-index keys broke."""
    import pickle

    from repro.relational import datagen
    from repro.relational.planner import executor, tpch

    tabs = datagen.gen_all(0.01)
    pq = tpch.q3()
    tables = {t: tabs[t] for t in pq.tables}
    catalog = {t: tables[t].capacity for t in pq.tables}

    plan_cold = pq.plan(catalog, 8)
    plan_re = pq.plan(catalog, 8)          # fresh replan, new identities
    plan_disk = pickle.loads(pickle.dumps(plan_cold))  # cached reload

    reports = []
    results = []
    for plan in (plan_cold, plan_re, plan_disk):
        run = executor.compile_plan(plan, tables)
        raw, qt = run.collect(run.dispatch())
        results.append(pq.finalize(raw))
        reports.append(qt.exchange_report())

    base = reports[0]
    assert set(base) == {"shuffle[o_orderkey]#0", "shuffle[l_orderkey]#1"}
    for rep in reports[1:]:
        assert list(rep) == list(base), (list(rep), list(base))
        for k in base:
            for field in base[k]:
                np.testing.assert_array_equal(
                    np.asarray(base[k][field]), np.asarray(rep[k][field]),
                    err_msg=f"{k}.{field} differs across plan lifecycles",
                )
    for got in results[1:]:
        for k in results[0]:
            np.testing.assert_array_equal(
                np.asarray(results[0][k]), np.asarray(got[k])
            )
    print("PASS exchange_report")


def _streamed_vs_resident(pq, sources, ctx):
    from repro.relational.planner.executor import execute_plan
    from repro.relational.planner.stream import compile_plan_streamed

    mat = {t: sources[t].materialize() for t in pq.tables}
    catalog = {t: sources[t].capacity for t in pq.tables}
    plan = pq.plan(catalog, ctx.num_shards)
    oracle = pq.finalize(execute_plan(plan, mat))
    run = compile_plan_streamed(plan, sources, ctx)
    return oracle, pq.finalize(run()), run.stats, plan


def _assert_close(oracle, got):
    if not isinstance(oracle, dict):
        oracle, got = {"r": oracle}, {"r": got}
    for k in oracle:
        o, g = np.asarray(oracle[k]), np.asarray(got[k])
        if o.dtype.kind == "f":
            np.testing.assert_allclose(g, o, rtol=1e-3, err_msg=k)
        else:
            np.testing.assert_array_equal(g, o, err_msg=k)


def scenario_oocore_streamed():
    """Q17/Q18 morsel-streamed over 8 shards == in-memory run, same mesh.

    The streamed table is chunked so only one morsel's shard slice is
    device-resident at a time; a device_row_budget below the full table
    capacity proves the in-memory path could not have run."""
    from repro.relational import datagen
    from repro.relational.planner.tpch import q17, q18
    from repro.relational.source import MorselView, as_source

    tabs = datagen.gen_all(0.01)
    li = tabs["lineitem"]
    budget = li.capacity // 2
    ctx = Ctx(num_shards=8, device_row_budget=budget)
    assert li.capacity > budget

    src17 = {"lineitem": MorselView(li, morsel_rows=4096),
             "part": as_source(tabs["part"])}
    oracle, got, stats, _ = _streamed_vs_resident(q17(), src17, ctx)
    _assert_close(oracle, got)
    assert stats["passes"] == 2 and stats["spilled_rows"] == 0

    src18 = {"lineitem": MorselView(li, morsel_rows=4096),
             "orders": as_source(tabs["orders"]),
             "customer": as_source(tabs["customer"])}
    oracle, got, stats, _ = _streamed_vs_resident(q18(), src18, ctx)
    _assert_close(oracle, got)
    assert len(np.asarray(got["o_orderkey"]))  # non-vacuous top-k
    print("PASS oocore_streamed")


def scenario_oocore_spill():
    """Forced exchange overflow: without spill the run raises; with
    ``spill=True`` the overflow lands in the host overflow partition, drains
    back through the same exchange, and the result matches the no-pressure
    run bit-for-bit."""
    from repro.relational import datagen
    from repro.relational.planner.stream import compile_plan_streamed
    from repro.relational.planner.tpch import q18
    from repro.relational.source import MorselView, as_source

    tabs = datagen.gen_all(0.01)
    pq = q18()
    sources = {"lineitem": MorselView(tabs["lineitem"], morsel_rows=4096),
               "orders": as_source(tabs["orders"]),
               "customer": as_source(tabs["customer"])}
    oracle, got, stats, plan = _streamed_vs_resident(
        pq, sources, Ctx(num_shards=8))
    _assert_close(oracle, got)
    assert stats["spilled_rows"] == 0

    # Q18 shuffles the unfiltered lineitem stream by l_orderkey: a 16-row
    # message capacity guarantees overflow on every morsel.
    try:
        compile_plan_streamed(
            plan, sources, Ctx(num_shards=8, exchange_rows=16))()
    except RuntimeError as e:
        assert "dropped" in str(e), e
    else:
        raise AssertionError("overflow without spill must raise")

    run = compile_plan_streamed(
        plan, sources, Ctx(num_shards=8, exchange_rows=16, spill=True))
    spilled = pq.finalize(run())
    assert run.stats["spilled_rows"] > 0, run.stats
    assert run.stats["drain_rounds"] > 0, run.stats
    for k in oracle:
        np.testing.assert_array_equal(
            np.asarray(spilled[k]), np.asarray(oracle[k]), err_msg=k)
    print("PASS oocore_spill")


def scenario_traced_query():
    """The telemetry-spine acceptance run: ONE traced streamed Q17 over 8
    shards yields a Perfetto-loadable trace whose spans cover
    plan/compile/pass/morsel/exchange, whose per-edge measured wire bytes
    sit inside the 2x byte-model bound with a model-error ratio reported
    per edge — and tracing observes without perturbing: the result is
    bit-identical to the untraced run and planning happened exactly as
    often (the trace knob is payload, not identity)."""
    import json

    from repro.obs.export import chrome_trace_events, tracer_to_dict
    from repro.obs.model_check import assert_bytes_within, model_report
    from repro.obs.trace import Tracer
    from repro.relational import datagen
    from repro.relational import stats as rstats
    from repro.relational.context import StatsMode
    from repro.relational.planner import tpch
    from repro.relational.planner.physical import plan_physical

    tabs = datagen.gen_all(0.01)
    pq = tpch.q17()
    tables = {t: tabs[t] for t in pq.tables}
    base = Ctx(
        num_shards=8, morsel_rows=4096,
        stats_mode=StatsMode.PROFILE,
        stats_profile=rstats.collect_stats(tables),
    )
    before = plan_physical.calls
    want = tpch.run_query(pq, tables, base)            # tracing OFF
    per_run = plan_physical.calls - before

    tracer = Tracer()
    traced = base.with_(trace=tracer)
    assert traced == base and hash(traced) == hash(base)  # same cache keys
    got = tpch.run_query(pq, tables, traced)           # tracing ON
    assert plan_physical.calls - before == 2 * per_run, "tracing replanned"
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # the span hierarchy is complete: plan -> build -> execute, with the
    # streamed runner's pass/morsel spans inside; the exchange edges live
    # in the QueryTrace record, not as spans of made-up durations
    fams = {s.name for root in tracer.spans for s in root.walk()}
    assert {"repro.plan", "repro.build", "repro.execute", "repro.pass",
            "repro.morsel"} <= fams, fams
    assert all(f.startswith("repro.") for f in fams), fams
    assert all(s.args["query"] == "q17"
               for root in tracer.spans for s in root.walk()), "untagged span"

    # one QueryTrace, a model-error ratio per edge, bytes inside the gate
    (qt,) = tracer.query_traces
    assert qt.query == "q17" and qt.edges
    rep = model_report(qt)
    assert set(rep["edges"]) == {e.key for e in qt.edges}
    assert all(v["byte_model_err"] is not None for v in rep["edges"].values())
    assert_bytes_within(qt)  # the same 2x bound CI gates

    # Perfetto-loadable: jsonable, B/E matched per track, sorted timestamps
    json.dumps(tracer_to_dict(tracer, process_name="driver"))
    dur = [e for e in chrome_trace_events(tracer) if e["ph"] in ("B", "E")]
    assert [e["ts"] for e in dur] == sorted(e["ts"] for e in dur)
    depth = 0
    for e in dur:
        depth += 1 if e["ph"] == "B" else -1
        assert depth >= 0
    assert depth == 0 and len(dur) >= 2 * 6
    print("PASS traced_query")


def scenario_qserve_traced_mix():
    """The exchange-report race, fixed at the source: one serve round
    running Q3 and Q17 through MEMOIZED executors returns a per-request
    QueryTrace that carries its OWN query's edges.  The old
    ``run.exchange_report`` function attribute was clobbered by whichever
    overlapped run finalized last — under the engine's async dispatch a Q3
    request could read Q17's report."""
    from repro.obs.trace import Tracer
    from repro.relational import datagen
    from repro.relational.planner import tpch
    from repro.relational.planner.plan_cache import PlanCache
    from repro.serve import QueryRequest, QueryServeEngine

    tabs = datagen.gen_all(0.01)
    templates = [tpch.q3(), tpch.q17()]
    names = sorted({t for pq in templates for t in pq.tables})
    tracer = Tracer()
    engine = QueryServeEngine(
        {n: tabs[n] for n in names}, Ctx(num_shards=8, trace=tracer),
        num_slots=2, cache=PlanCache(), templates=templates,
    )
    # two interleaved copies of each template: every round overlaps a Q3
    # and a Q17 through the same memoized runners
    done = engine.serve(
        [QueryRequest("t", pq) for _ in range(2) for pq in templates]
    )
    expect = {
        "q3": {"shuffle[o_orderkey]#0", "shuffle[l_orderkey]#1"},
        "q17": {"shuffle[l_partkey]#0"},
    }
    for r in done:
        assert r.trace is not None and r.trace.query == r.query.name
        assert {e.key for e in r.trace.edges} == expect[r.query.name], (
            r.query.name, [e.key for e in r.trace.edges],
        )
    assert len(tracer.query_traces) == len(done) == 4
    cats = {s.cat for root in tracer.spans for s in root.walk()}
    assert "serve" in cats, cats
    print("PASS qserve_traced_mix")


SCENARIOS = {
    name.removeprefix("scenario_"): fn
    for name, fn in list(globals().items())
    if name.startswith("scenario_")
}

if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    names = SCENARIOS if which == "all" else [which]
    for n in names:
        SCENARIOS[n]()
