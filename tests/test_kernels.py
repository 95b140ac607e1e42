"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as flash_kernel
from repro.kernels.ssd_scan import ssd_scan as ssd_kernel

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# flash_attention: shape × dtype × causal sweep.
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, H, KH, Sq, Sk, D, causal, bq, bk)
    (2, 4, 2, 256, 256, 64, True, 128, 128),
    (1, 8, 8, 128, 128, 32, True, 64, 64),
    (2, 4, 1, 128, 256, 64, False, 64, 128),
    (1, 2, 2, 512, 512, 128, True, 128, 128),
    (1, 12, 4, 128, 128, 64, True, 128, 128),
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(case, dtype):
    B, H, KH, Sq, Sk, D, causal, bq, bk = case
    q = jax.random.normal(KEY, (B, H, Sq, D), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, KH, Sk, D), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, KH, Sk, D), dtype)
    out = flash_kernel(q, k, v, causal=causal, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_flash_vjp_grads_match_sdpa():
    B, S, H, KH, D = 2, 256, 4, 2, 64
    q = jax.random.normal(KEY, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, KH, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, KH, D))
    from repro.models.layers import sdpa

    g_ref = jax.grad(lambda q: sdpa(q, k, v, causal=True).sum())(q)
    g_fl = jax.grad(lambda q: ops.flash_attention_vjp(q, k, v, True).sum())(q)
    np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_ref), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ssd_scan: shape sweep + state chaining.
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (B, L, H, P, N, chunk, hb)
    (2, 32, 8, 16, 32, 8, 4),
    (1, 64, 16, 8, 16, 16, 8),
    (2, 16, 4, 32, 64, 16, 4),
    (1, 128, 8, 64, 128, 32, 8),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_matches_oracle(case):
    B, L, H, P, N, chunk, hb = case
    x = jax.random.normal(KEY, (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1), (B, L, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (H,)))
    Bm = jax.random.normal(jax.random.fold_in(KEY, 3), (B, L, 1, N))
    Cm = jax.random.normal(jax.random.fold_in(KEY, 4), (B, L, 1, N))
    y, fin = ssd_kernel(x, dt, A, Bm, Cm, chunk=chunk, head_block=hb)
    yr, finr = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(finr), rtol=2e-4, atol=2e-4)


def test_ssd_scan_initial_state_chaining():
    B, L, H, P, N = 2, 32, 4, 16, 32
    x = jax.random.normal(KEY, (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(KEY, (B, L, H)))
    A = -jnp.exp(jax.random.normal(KEY, (H,)))
    Bm = jax.random.normal(KEY, (B, L, 1, N))
    Cm = jax.random.normal(jax.random.fold_in(KEY, 5), (B, L, 1, N))
    y_all, s_all = ssd_kernel(x, dt, A, Bm, Cm, chunk=8, head_block=4)
    _, s_half = ssd_kernel(x[:, :16], dt[:, :16], A, Bm[:, :16], Cm[:, :16], chunk=8, head_block=4)
    y2, s2 = ssd_kernel(
        x[:, 16:], dt[:, 16:], A, Bm[:, 16:], Cm[:, 16:], chunk=8, head_block=4,
        initial_state=s_half,
    )
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_all[:, 16:]), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_all), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# hash_partition / moe_dispatch.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,P,blk", [(512, 8, 128), (1024, 16, 256), (256, 3, 256)])
def test_hash_partition_matches_oracle(T, P, blk):
    keys = jax.random.randint(KEY, (T,), 0, 1 << 30)
    pid, hist = ops.hash_partition(keys, P, block=blk)
    pid_r, hist_r = ref.hash_partition_ref(keys, P, block=min(blk, T))
    np.testing.assert_array_equal(np.asarray(pid), np.asarray(pid_r))
    np.testing.assert_array_equal(np.asarray(hist.sum(0)), np.asarray(hist_r.sum(0)))


@pytest.mark.parametrize("T,E,C", [(512, 16, 8), (2048, 64, 24), (256, 4, 1000)])
def test_moe_dispatch_matches_oracle(T, E, C):
    dest = jax.random.randint(KEY, (T,), 0, E)
    slot, counts = ops.moe_dispatch(dest, E, C)
    slot_r, counts_r = ref.moe_dispatch_ref(dest, E, C)
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(slot_r))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_r))


@pytest.mark.parametrize("T,B,blk", [(512, 9, 256), (300, 5, 128), (64, 3, 256),
                                     (1000, 17, 256), (2048, 257, 256)])
def test_partition_ranks_matches_arrival_order(T, B, blk):
    dest = jax.random.randint(jax.random.fold_in(KEY, T), (T,), 0, B)
    rank, counts = ops.partition_ranks(dest, B, block=blk)
    with ops.use_kernels(False):
        rank_r, counts_r = ops.partition_ranks(dest, B, block=blk)
    np.testing.assert_array_equal(np.asarray(rank), np.asarray(rank_r))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_r))
    # oracle: arrival-order rank within each destination
    d = np.asarray(dest)
    want = np.zeros(T, np.int64)
    seen: dict = {}
    for t in range(T):
        want[t] = seen.get(d[t], 0)
        seen[d[t]] = want[t] + 1
    np.testing.assert_array_equal(np.asarray(rank), want)
    np.testing.assert_array_equal(np.asarray(counts), np.bincount(d, minlength=B))


@pytest.mark.parametrize("T,P", [(512, 8), (300, 5), (64, 3), (1024, 256)])
def test_hash_partition_ranks_fused_matches_ref(T, P):
    keys = jax.random.randint(KEY, (T,), 0, 1 << 30)
    valid = jax.random.bernoulli(jax.random.fold_in(KEY, 9), 0.8, (T,)).astype(jnp.int32)
    dest, rank, counts = ops.hash_partition_ranks(keys, valid, P)
    with ops.use_kernels(False):
        dest_r, rank_r, counts_r = ops.hash_partition_ranks(keys, valid, P)
    np.testing.assert_array_equal(np.asarray(dest), np.asarray(dest_r))
    np.testing.assert_array_equal(np.asarray(rank), np.asarray(rank_r))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_r))
    # dest matches the hash mod P for valid rows, overflow bin for invalid
    pid = np.asarray(ref.fibonacci_hash_ref(keys) % jnp.uint32(P))
    want = np.where(np.asarray(valid) != 0, pid, P)
    np.testing.assert_array_equal(np.asarray(dest), want)


def test_partition_pack_kernel_matches_ref_oracle():
    from repro.kernels.hash_partition import partition_pack

    dest = jax.random.randint(KEY, (512,), 0, 7)
    hist, local = partition_pack(dest, 7, block=128)
    hist_r, local_r = ref.partition_pack_ref(dest, 7, block=128)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(hist_r))
    np.testing.assert_array_equal(np.asarray(local), np.asarray(local_r))


def test_use_kernels_toggle():
    keys = jax.random.randint(KEY, (256,), 0, 1 << 30)
    with ops.use_kernels(False):
        assert not ops.kernels_enabled()
        pid, _ = ops.hash_partition(keys, 8)
    with ops.use_kernels(True):
        pid2, _ = ops.hash_partition(keys, 8)
    np.testing.assert_array_equal(np.asarray(pid), np.asarray(pid2))
