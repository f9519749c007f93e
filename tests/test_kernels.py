"""Per-kernel validation: Pallas (interpret=True on CPU) vs the pure-jnp
oracles in kernels/ref.py, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import heap
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.knn_merge import knn_merge_blocked, knn_pool_insert_blocked
from repro.kernels.l2_blocked import pairwise_sq_l2_blocked, vmem_bytes


# ---------------------------------------------------------------------------
# l2_blocked (paper §3.3)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,d", [
    (128, 128, 128), (200, 130, 96), (64, 256, 300),
    (1, 128, 8), (128, 1, 513), (37, 41, 7),
])
def test_l2_blocked_shapes(m, n, d):
    k1, k2 = jax.random.split(jax.random.key(m * 1000 + n))
    a = jax.random.normal(k1, (m, d), jnp.float32)
    b = jax.random.normal(k2, (n, d), jnp.float32)
    out = pairwise_sq_l2_blocked(a, b, tm=128, tn=128, tk=128,
                                 interpret=True)
    np.testing.assert_allclose(out, ref.pairwise_sq_l2(a, b),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_l2_blocked_dtypes(dtype):
    k1, k2 = jax.random.split(jax.random.key(0))
    a = jax.random.normal(k1, (96, 64)).astype(dtype)
    b = jax.random.normal(k2, (80, 64)).astype(dtype)
    out = pairwise_sq_l2_blocked(a, b, tm=128, tn=128, tk=128,
                                 interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(out, ref.pairwise_sq_l2(a, b),
                               rtol=tol, atol=tol)


def test_l2_blocked_vs_diff_form():
    """The norm-expansion kernel vs the paper's diff-FMA oracle — the
    numerics assumption change (DESIGN.md #2): clamp guards cancellation."""
    k1, k2 = jax.random.split(jax.random.key(3))
    a = jax.random.normal(k1, (64, 32), jnp.float32)
    b = a + 1e-4 * jax.random.normal(k2, (64, 32), jnp.float32)
    out = pairwise_sq_l2_blocked(a, b, interpret=True, tk=128)
    want = ref.pairwise_sq_l2_diff(a, b)
    assert float(jnp.min(out)) >= 0.0
    np.testing.assert_allclose(out, want, atol=1e-4)


def test_l2_blocked_tile_sweep():
    k1, k2 = jax.random.split(jax.random.key(1))
    a = jax.random.normal(k1, (130, 100), jnp.float32)
    b = jax.random.normal(k2, (70, 100), jnp.float32)
    want = ref.pairwise_sq_l2(a, b)
    for tm, tn, tk in [(8, 128, 128), (128, 8, 128), (16, 16, 256)]:
        out = pairwise_sq_l2_blocked(a, b, tm=tm, tn=tn, tk=tk,
                                     interpret=True)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-4)


def test_vmem_budget():
    # default tiles must fit v5e VMEM (~128 MiB, budget half for pipeline)
    assert vmem_bytes(128, 128, 512) < 64 * 1024 * 1024


# ---------------------------------------------------------------------------
# knn_merge
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,c", [(64, 8, 12), (100, 20, 7), (256, 4, 40)])
def test_knn_merge_shapes(n, k, c):
    key = jax.random.key(n + k)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    cur_d = jnp.sort(jax.random.uniform(k1, (n, k)), axis=1)
    cur_i = jax.random.randint(k2, (n, k), 0, 10 * n)
    cand_d = jax.random.uniform(k3, (n, c))
    cand_i = jax.random.randint(k4, (n, c), -1, 10 * n)
    got = knn_merge_blocked(cur_d, cur_i, cand_d, cand_i, tm=32,
                            interpret=True)
    want = ref.knn_merge(cur_d, cur_i, cand_d, cand_i)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


def test_knn_merge_dedup():
    """Candidates already present in the list must not be double-counted."""
    cur_d = jnp.array([[0.1, 0.2, jnp.inf]])
    cur_i = jnp.array([[5, 7, -1]], jnp.int32)
    cand_d = jnp.array([[0.05, 0.1, 0.3]])
    cand_i = jnp.array([[7, 5, 9]], jnp.int32)    # 7 and 5 are dups
    d, i, upd = knn_merge_blocked(cur_d, cur_i, cand_d, cand_i, tm=8,
                                  interpret=True)
    assert int(upd[0]) == 1                       # only 9 accepted
    assert 9 in np.asarray(i[0])
    assert sorted(np.asarray(i[0]).tolist()) == [5, 7, 9]


def _pool_and_candidates(beam, width, rows=24, seed=0):
    """Sorted pools and the search's select of a 3*width candidate tile,
    with every case the insert must get right: tied distances (integer
    draws), repeated candidate ids, candidates already in the pool, +inf
    pool tails, a row with no valid candidate (row 0), a row whose
    candidates fill its empty pool (row 1) and a full pool (row 2)."""
    rng = np.random.RandomState(seed + beam + width)
    ids = 4 * beam
    pool_i = np.stack([rng.choice(ids, beam, replace=False)
                       for _ in range(rows)]).astype(np.int32)
    pool_d = np.sort(rng.randint(0, 30, (rows, beam)), 1).astype(np.float32)
    fill = rng.randint(0, beam + 1, rows)
    fill[1], fill[2] = 0, beam
    for r in range(rows):
        pool_d[r, fill[r]:], pool_i[r, fill[r]:] = np.inf, -1
    pool_new = (rng.rand(rows, beam) < 0.5) & (pool_i >= 0)
    tile_d = rng.randint(0, 30, (rows, 3 * width)).astype(np.float32)
    tile_i = rng.randint(-1, ids, (rows, 3 * width)).astype(np.int32)
    tile_i[:, 1::5] = tile_i[:, ::5][:, :tile_i[:, 1::5].shape[1]]
    tile_i[2:, 2::4] = pool_i[2:, :tile_i[:, 2::4].shape[1]]
    tile_i[0] = -1
    tile_i[1] = np.arange(3 * width)            # distinct, all valid
    cd, ci = ref.knn_join_select(jnp.asarray(tile_d), jnp.asarray(tile_i),
                                 jnp.asarray(pool_d[:, -1]), width)
    return (jnp.asarray(pool_d), jnp.asarray(pool_i), jnp.asarray(pool_new),
            cd, ci)


@pytest.mark.parametrize("beam,width", [(256, 80), (128, 80), (32, 32)])
def test_pool_insert_matches_merge(beam, width):
    """The search's pool insert equals today's round merge bit for bit:
    the select's candidates through ref.knn_merge with the flags of
    heap.merge_kernel."""
    pool_d, pool_i, pool_new, cd, ci = _pool_and_candidates(beam, width)
    want, upd = heap.merge_kernel(heap.NeighborLists(pool_d, pool_i,
                                                     pool_new),
                                  cd, ci, backend="ref")
    d, i, new, ins, off = knn_pool_insert_blocked(
        pool_d, pool_i, pool_new.astype(jnp.int32), cd, ci, tm=8,
        interpret=True)
    np.testing.assert_array_equal(d, want.dist)
    np.testing.assert_array_equal(i, want.idx)
    np.testing.assert_array_equal(new > 0, want.new)
    np.testing.assert_array_equal(ins, upd)
    np.testing.assert_array_equal(off, ref.knn_pool_insert(
        pool_d, pool_i, pool_new, cd, ci)[4])
    assert int(off[0]) == 0 and int(ins[0]) == 0          # nothing offered
    assert int(ins[1]) == min(beam, width)                # fills the pool
    assert bool(jnp.all(i[1] >= 0)) == (width >= beam)
    assert int(jnp.sum(off)) < int(jnp.sum(ci >= 0))      # dedup dropped
    assert bool(jnp.all(ins <= off)) and int(jnp.sum(ins)) > 0


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [
    dict(causal=True, window=None, softcap=None),
    dict(causal=True, window=64, softcap=None),
    dict(causal=True, window=None, softcap=20.0),
    dict(causal=False, window=None, softcap=None),
])
def test_flash_attention_modes(cfg):
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    B, L, H, Hkv, Dh = 2, 256, 4, 2, 32
    q = jax.random.normal(k1, (B, L, H, Dh), jnp.float32)
    k = jax.random.normal(k2, (B, L, Hkv, Dh), jnp.float32)
    v = jax.random.normal(k3, (B, L, Hkv, Dh), jnp.float32)
    got = flash_attention(q, k, v, tq=128, tk=128, interpret=True, **cfg)
    want = ref.attention(q, k, v, **cfg)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_flash_attention_gqa_fold():
    """kv-head folding in the index map must match repeat-based ref."""
    k1, k2, k3 = jax.random.split(jax.random.key(5), 3)
    B, L, H, Hkv, Dh = 1, 128, 8, 2, 16
    q = jax.random.normal(k1, (B, L, H, Dh))
    k = jax.random.normal(k2, (B, L, Hkv, Dh))
    v = jax.random.normal(k3, (B, L, Hkv, Dh))
    got = flash_attention(q, k, v, tq=128, tk=128, interpret=True)
    want = ref.attention(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
