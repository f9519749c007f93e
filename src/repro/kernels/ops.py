"""Public jit'd wrappers for the Pallas kernels with backend dispatch.

On TPU the Pallas kernels run compiled (interpret=False); everywhere else
(this container is CPU) the same kernel bodies execute under interpret=True
when explicitly requested, and by default we dispatch to the pure-jnp
oracles in ref.py, which are numerically identical and compile to efficient
HLO. Tests exercise the interpret=True path against the oracles across
shape/dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.knn_join import (
    knn_join_dists_blocked,
    knn_join_select_blocked,
)
from repro.kernels.knn_merge import (
    knn_compact_blocked,
    knn_compact_rows_blocked,
    knn_merge_blocked,
    knn_merge_rows_blocked,
    knn_pool_insert_blocked,
)
from repro.kernels.knn_search import knn_search_dists_blocked
from repro.kernels.l2_blocked import pairwise_sq_l2_blocked
from repro.kernels.l2_quant import (
    knn_join_dists_bf16_blocked,
    knn_join_dists_q8_blocked,
    knn_search_dists_bf16_blocked,
    knn_search_dists_q8_blocked,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pairwise_sq_l2(
    a: jax.Array,
    b: jax.Array,
    *,
    backend: str = "auto",   # auto | pallas | interpret | ref
    tm: int = 128,
    tn: int = 128,
    tk: int = 512,
) -> jax.Array:
    """Pairwise squared-l2 distances, (M, D) x (N, D) -> (M, N) f32."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return pairwise_sq_l2_blocked(a, b, tm=tm, tn=tn, tk=tk)
    if backend == "interpret":
        return pairwise_sq_l2_blocked(a, b, tm=tm, tn=tn, tk=tk, interpret=True)
    return ref.pairwise_sq_l2(a, b)


def centroid_assign(
    q: jax.Array,
    q2: jax.Array,
    cent: jax.Array,
    c2: jax.Array,
    *,
    t: int = 1,
    backend: str = "auto",
):
    """Router centroid assignment: top-``t`` nearest centroids per row,
    (m, dp) x (c, dp) -> (dist (m, t) ascending, idx (m, t)). The distance
    tile reuses the blocked pairwise-l2 kernel (pallas/interpret) or its
    norm-expansion oracle (ref); the partial top-k reduction is shared."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend in ("pallas", "interpret"):
        d = pairwise_sq_l2_blocked(q, cent, interpret=backend == "interpret")
        neg, idx = jax.lax.top_k(-d, t)
        import jax.numpy as _jnp
        return _jnp.maximum(-neg, 0.0), idx.astype(_jnp.int32)
    return ref.centroid_assign(q, q2, cent, c2, t)


def knn_join_dists(
    xg: jax.Array,
    x2g: jax.Array,
    ids: jax.Array,
    *,
    cn: int,
    backend: str = "auto",
):
    """Fused local-join pair distances: (n, C, dp) gathered candidate
    features -> ((n, C, C) masked sq-l2 tensor, (n,) valid-pair counts)."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return knn_join_dists_blocked(xg, x2g, ids, cn=cn)
    if backend == "interpret":
        return knn_join_dists_blocked(xg, x2g, ids, cn=cn, interpret=True)
    return ref.knn_join_dists(xg, x2g, ids, cn)


def knn_join_select(
    gd: jax.Array,
    gi: jax.Array,
    kth: jax.Array,
    *,
    c: int,
    backend: str = "auto",
):
    """Receiver-side prefilter + best-c selection of gathered join pairs."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return knn_join_select_blocked(gd, gi, kth, c=c)
    if backend == "interpret":
        return knn_join_select_blocked(gd, gi, kth, c=c, interpret=True)
    return ref.knn_join_select(gd, gi, kth, c)


def knn_search_dists(
    q: jax.Array,
    q2: jax.Array,
    cg: jax.Array,
    c2g: jax.Array,
    ids: jax.Array,
    *,
    backend: str = "auto",
):
    """Fused serving search: blocked query-time candidate distance tile
    ((nq, W, dp) gathered candidate features -> (nq, W) masked sq-l2)."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return knn_search_dists_blocked(q, q2, cg, c2g, ids)
    if backend == "interpret":
        return knn_search_dists_blocked(q, q2, cg, c2g, ids, interpret=True)
    return ref.knn_search_dists(q, q2, cg, c2g, ids)


def knn_search_dists_q8(
    qq: jax.Array,
    qscale: jax.Array,
    q2: jax.Array,
    cq: jax.Array,
    cscale: jax.Array,
    c2g: jax.Array,
    ids: jax.Array,
    *,
    backend: str = "auto",
):
    """Quantized serving scoring tile (int8 rows + per-row fp32 scales):
    (nq, W, dp) int8 gathered candidates -> (nq, W) masked sq-l2 with the
    scale application and norm expansion fused into the epilogue."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return knn_search_dists_q8_blocked(qq, qscale, q2, cq, cscale, c2g,
                                           ids)
    if backend == "interpret":
        return knn_search_dists_q8_blocked(qq, qscale, q2, cq, cscale, c2g,
                                           ids, interpret=True)
    return ref.knn_search_dists_q8(qq, qscale, q2, cq, cscale, c2g, ids)


def knn_search_dists_bf16(
    q: jax.Array,
    q2: jax.Array,
    cg: jax.Array,
    c2g: jax.Array,
    ids: jax.Array,
    *,
    backend: str = "auto",
):
    """bf16 serving scoring tile: same contract as knn_search_dists with
    bf16 operands fed to the MXU (fp32 accumulation)."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return knn_search_dists_bf16_blocked(q, q2, cg, c2g, ids)
    if backend == "interpret":
        return knn_search_dists_bf16_blocked(q, q2, cg, c2g, ids,
                                             interpret=True)
    return ref.knn_search_dists_bf16(q, q2, cg, c2g, ids)


def knn_join_dists_q8(
    xq: jax.Array,
    xscale: jax.Array,
    x2g: jax.Array,
    ids: jax.Array,
    *,
    cn: int,
    backend: str = "auto",
):
    """Quantized local-join scoring tensor (int8): (n, C, dp) int8
    gathered candidates -> ((n, C, C) masked sq-l2, (n,) valid-pair
    counts). Same mask/evals contract as knn_join_dists."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return knn_join_dists_q8_blocked(xq, xscale, x2g, ids, cn=cn)
    if backend == "interpret":
        return knn_join_dists_q8_blocked(xq, xscale, x2g, ids, cn=cn,
                                         interpret=True)
    return ref.knn_join_dists_q8(xq, xscale, x2g, ids, cn)


def knn_join_dists_bf16(
    xg: jax.Array,
    x2g: jax.Array,
    ids: jax.Array,
    *,
    cn: int,
    backend: str = "auto",
):
    """bf16 local-join scoring tensor: same contract as knn_join_dists
    with bf16 operands fed to the MXU (fp32 accumulation)."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return knn_join_dists_bf16_blocked(xg, x2g, ids, cn=cn)
    if backend == "interpret":
        return knn_join_dists_bf16_blocked(xg, x2g, ids, cn=cn,
                                           interpret=True)
    return ref.knn_join_dists_bf16(xg, x2g, ids, cn)


def knn_merge(
    cur_dist: jax.Array,
    cur_idx: jax.Array,
    cand_dist: jax.Array,
    cand_idx: jax.Array,
    *,
    backend: str = "auto",
):
    """Merge candidates into sorted bounded k-NN lists (dedup by id)."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return knn_merge_blocked(cur_dist, cur_idx, cand_dist, cand_idx)
    if backend == "interpret":
        return knn_merge_blocked(
            cur_dist, cur_idx, cand_dist, cand_idx, interpret=True
        )
    return ref.knn_merge(cur_dist, cur_idx, cand_dist, cand_idx)


def knn_pool_insert(
    pool_dist: jax.Array,
    pool_idx: jax.Array,
    pool_new: jax.Array,
    cand_dist: jax.Array,
    cand_idx: jax.Array,
    *,
    backend: str = "auto",
):
    """The graph search's round merge: candidates into sorted pools with
    their ``new`` flags (bool). Returns (dist, idx, new, inserted,
    offered)."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "ref":
        return ref.knn_pool_insert(pool_dist, pool_idx, pool_new, cand_dist,
                                   cand_idx)
    d, i, new, ins, off = knn_pool_insert_blocked(
        pool_dist, pool_idx, pool_new.astype(jnp.int32), cand_dist,
        cand_idx, interpret=backend == "interpret")
    return d, i, new > 0, ins, off


def knn_compact(
    cur_dist: jax.Array,
    cur_idx: jax.Array,
    drop: jax.Array,
    *,
    backend: str = "auto",
):
    """Drop masked entries from sorted bounded k-NN lists (tombstone purge)."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return knn_compact_blocked(cur_dist, cur_idx, drop)
    if backend == "interpret":
        return knn_compact_blocked(cur_dist, cur_idx, drop, interpret=True)
    return ref.knn_compact(cur_dist, cur_idx, drop)


def knn_merge_rows(
    cur_dist: jax.Array,
    cur_idx: jax.Array,
    rows: jax.Array,
    cand_dist: jax.Array,
    cand_idx: jax.Array,
    *,
    backend: str = "auto",
):
    """Frontier merge: candidates target ``rows`` only (gather -> blocked
    merge kernel over the padded chunk -> scatter). -1 rows are padding."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return knn_merge_rows_blocked(cur_dist, cur_idx, rows, cand_dist,
                                      cand_idx)
    if backend == "interpret":
        return knn_merge_rows_blocked(
            cur_dist, cur_idx, rows, cand_dist, cand_idx, interpret=True
        )
    return ref.knn_merge_rows(cur_dist, cur_idx, rows, cand_dist, cand_idx)


def knn_compact_rows(
    cur_dist: jax.Array,
    cur_idx: jax.Array,
    rows: jax.Array,
    drop: jax.Array,
    *,
    backend: str = "auto",
):
    """Frontier compact: drop masked entries from ``rows`` only (gather ->
    blocked compact kernel over the padded chunk -> scatter)."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return knn_compact_rows_blocked(cur_dist, cur_idx, rows, drop)
    if backend == "interpret":
        return knn_compact_rows_blocked(cur_dist, cur_idx, rows, drop,
                                        interpret=True)
    return ref.knn_compact_rows(cur_dist, cur_idx, rows, drop)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    q_offset: int = 0,
    backend: str = "auto",
) -> jax.Array:
    """Blocked attention. The model stack calls models.attention (chunked
    scan) for large shapes; this wrapper is the kernel-level entry point."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "pallas":
        return flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset,
        )
    if backend == "interpret":
        return flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, interpret=True,
            tq=min(128, q.shape[1]), tk=min(128, k.shape[1]),
        )
    return ref.attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset,
    )
