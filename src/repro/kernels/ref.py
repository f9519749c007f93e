"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground-truth implementations the kernels are validated
against (tests sweep shapes/dtypes and ``assert_allclose`` kernel vs ref).
They are also the CPU fallback used when running on a non-TPU backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Pairwise squared-l2 distance (paper §3.3, "blocked")
# ---------------------------------------------------------------------------

def pairwise_sq_l2_diff(a: jax.Array, b: jax.Array) -> jax.Array:
    """Direct diff-square-sum form — the paper's AVX FMA ladder.

    a: (M, D), b: (N, D) -> (M, N) float32. Numerically the most faithful
    form (no cancellation); O(M*N*D) loads without blocking.
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    diff = a[:, None, :] - b[None, :, :]
    return jnp.sum(diff * diff, axis=-1)


def pairwise_sq_l2(a: jax.Array, b: jax.Array) -> jax.Array:
    """Norm-expansion form: ||a-b||^2 = ||a||^2 + ||b||^2 - 2 a.b^T.

    This is the MXU-friendly form the Pallas kernel implements. fp32
    accumulation, clamped at zero (cancellation guard).
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    a2 = jnp.sum(a * a, axis=-1)
    b2 = jnp.sum(b * b, axis=-1)
    ab = a @ b.T
    out = a2[:, None] + b2[None, :] - 2.0 * ab
    return jnp.maximum(out, 0.0)


def centroid_assign(
    q: jax.Array,      # (m, dp) rows to assign
    q2: jax.Array,     # (m,) cached squared norms
    cent: jax.Array,   # (c, dp) centroids
    c2: jax.Array,     # (c,) centroid squared norms
    t: int,            # top-t nearest centroids returned per row
) -> tuple[jax.Array, jax.Array]:
    """Top-``t`` nearest centroids per row: one norm-expansion distance
    tile + partial top-k. Returns (dist (m, t) ascending, idx (m, t)).
    Oracle for the router's centroid-assignment dispatch (kernels/ops.py
    routes the pallas/interpret backends through the blocked l2 kernel +
    the same top-k reduction)."""
    d = jnp.maximum(
        q2[:, None] + c2[None, :]
        - 2.0 * q.astype(jnp.float32) @ cent.astype(jnp.float32).T,
        0.0,
    )
    neg, idx = jax.lax.top_k(-d, t)
    return jnp.maximum(-neg, 0.0), idx.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Fused local join (paper §3.3 + §2 fused) — oracles for kernels/knn_join.py
# ---------------------------------------------------------------------------

_BIG = float(jnp.finfo(jnp.float32).max)


def _join_ok(ids: jax.Array, cn: int) -> jax.Array:
    """Join validity, shared by every join-distance oracle: at least one
    "new" endpoint, distinct slots, both occupied, distinct node ids."""
    c = ids.shape[1]
    slot = jnp.arange(c)
    ok = (slot[:, None] < cn) | (slot[None, :] < cn)
    ok &= slot[:, None] != slot[None, :]
    ok = ok[None]
    ok &= (ids[:, :, None] >= 0) & (ids[:, None, :] >= 0)
    ok &= ids[:, :, None] != ids[:, None, :]
    return ok


def knn_join_dists(
    xg: jax.Array,     # (n, C, dp) gathered candidate features
    x2g: jax.Array,    # (n, C) cached squared norms (0 on invalid slots)
    ids: jax.Array,    # (n, C) candidate node ids, -1 = invalid slot
    cn: int,           # width of the "new" candidate prefix
) -> tuple[jax.Array, jax.Array]:
    """Local-join pair-distance tensor: per row, squared-l2 between every
    candidate pair with at least one "new" endpoint, distinct slots and
    distinct ids; invalid pairs are +inf. Returns (dists (n, C, C),
    evals (n,) int32 — valid unordered pairs). Oracle for
    knn_join_dists_blocked."""
    ab = jnp.einsum(
        "ncd,ned->nce", xg.astype(jnp.float32), xg.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    dd = x2g[:, :, None] + x2g[:, None, :] - 2.0 * ab
    ok = _join_ok(ids, cn)
    out = jnp.where(ok, jnp.maximum(dd, 0.0), jnp.inf)
    evals = jnp.sum(ok.astype(jnp.int32), axis=(1, 2)) // 2
    return out, evals


def knn_join_select(
    gd: jax.Array,     # (n, W) gathered incoming pair distances (+inf pad)
    gi: jax.Array,     # (n, W) their candidate ids (-1 pad)
    kth: jax.Array,    # (n,) receiver k-th distance (prefilter threshold)
    c: int,            # output width (merge buffer size)
) -> tuple[jax.Array, jax.Array]:
    """Receiver-side prefilter + best-c selection: entries with
    ``gd < kth`` survive; the c smallest (stable on ties) come back as
    (dist (n, c) ascending, idx (n, c)) with (+inf, -1) fill. Oracle for
    knn_join_select_blocked."""
    w = gd.shape[1]
    pool = jnp.where((gi >= 0) & (gd < kth[:, None]), gd, _BIG)
    if c > w:
        pool = jnp.pad(pool, ((0, 0), (0, c - w)), constant_values=_BIG)
        gi = jnp.pad(gi, ((0, 0), (0, c - w)), constant_values=-1)
    neg, pos = jax.lax.top_k(-pool, c)
    d = -neg
    i = jnp.take_along_axis(gi, pos, axis=1)
    return (
        jnp.where(d < _BIG, d, jnp.inf),
        jnp.where(d < _BIG, i, -1),
    )


# ---------------------------------------------------------------------------
# Fused serving search (query-time §3.3) — oracle for kernels/knn_search.py
# ---------------------------------------------------------------------------

def knn_search_dists(
    q: jax.Array,      # (nq, dp) query block features
    q2: jax.Array,     # (nq,) hoisted query squared norms
    cg: jax.Array,     # (nq, W, dp) gathered candidate features
    c2g: jax.Array,    # (nq, W) cached candidate squared norms
    ids: jax.Array,    # (nq, W) candidate ids, -1 = invalid (incl. dead)
) -> jax.Array:
    """Query-time candidate distance tile: per query, squared-l2 to each of
    its W gathered candidates; invalid candidates (id -1 — unoccupied
    neighbor slots and tombstoned rows alike) come out +inf. Oracle for
    knn_search_dists_blocked."""
    ab = jnp.einsum(
        "qd,qwd->qw", q.astype(jnp.float32), cg.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    dd = q2[:, None] + c2g - 2.0 * ab
    return jnp.where(ids >= 0, jnp.maximum(dd, 0.0), jnp.inf)


# ---------------------------------------------------------------------------
# Quantized scoring tiles (two-stage distance path) — oracles for
# kernels/l2_quant.py. The int8 cross terms accumulate in fp32 here (the
# fast CPU path: the products are integers, exact in fp32 while the
# running sum stays under 2^24 — dp <= 1040, every shipped dim), which is
# bit-identical to the kernels' int32 MXU accumulation in that regime.
# ---------------------------------------------------------------------------

def knn_search_dists_q8(
    qq: jax.Array,     # (nq, dp) int8 query rows
    qscale: jax.Array,  # (nq,) query dequant scales
    q2: jax.Array,     # (nq,) quantized-query squared norms
    cq: jax.Array,     # (nq, W, dp) int8 gathered candidate rows
    cscale: jax.Array,  # (nq, W) candidate dequant scales
    c2g: jax.Array,    # (nq, W) cached quantized-candidate squared norms
    ids: jax.Array,    # (nq, W) candidate ids, -1 = invalid (incl. dead)
) -> jax.Array:
    """int8 query-time candidate distance tile with the dequant scales
    and norm expansion in the epilogue. Oracle for
    knn_search_dists_q8_blocked."""
    ab = jnp.einsum(
        "qd,qwd->qw", qq.astype(jnp.float32), cq.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    dd = q2[:, None] + c2g - 2.0 * (qscale[:, None] * cscale) * ab
    return jnp.where(ids >= 0, jnp.maximum(dd, 0.0), jnp.inf)


def knn_search_dists_bf16(
    q: jax.Array,      # (nq, dp) bf16 query rows
    q2: jax.Array,     # (nq,) bf16-rounded-query squared norms (f32)
    cg: jax.Array,     # (nq, W, dp) bf16 gathered candidate rows
    c2g: jax.Array,    # (nq, W) cached bf16-candidate squared norms
    ids: jax.Array,    # (nq, W) candidate ids, -1 = invalid (incl. dead)
) -> jax.Array:
    """bf16 query-time candidate distance tile, fp32 accumulation: the
    fp32 oracle applied to bf16-rounded rows (the oracle upcasts its
    operands anyway — only the kernel's MXU operand dtype differs).
    Oracle for knn_search_dists_bf16_blocked."""
    return knn_search_dists(q, q2, cg, c2g, ids)


def knn_join_dists_q8(
    xq: jax.Array,     # (n, C, dp) int8 gathered candidate rows
    xscale: jax.Array,  # (n, C) candidate dequant scales
    x2g: jax.Array,    # (n, C) cached quantized squared norms (0 invalid)
    ids: jax.Array,    # (n, C) candidate node ids, -1 = invalid slot
    cn: int,           # width of the "new" candidate prefix
) -> tuple[jax.Array, jax.Array]:
    """int8 local-join pair-distance tensor. Oracle for
    knn_join_dists_q8_blocked; same mask/evals contract as
    knn_join_dists."""
    xf = xq.astype(jnp.float32)
    ab = jnp.einsum("ncd,ned->nce", xf, xf,
                    preferred_element_type=jnp.float32)
    dd = x2g[:, :, None] + x2g[:, None, :] - 2.0 * (
        xscale[:, :, None] * xscale[:, None, :]
    ) * ab
    ok = _join_ok(ids, cn)
    out = jnp.where(ok, jnp.maximum(dd, 0.0), jnp.inf)
    return out, jnp.sum(ok.astype(jnp.int32), axis=(1, 2)) // 2


def knn_join_dists_bf16(
    xg: jax.Array,     # (n, C, dp) bf16 gathered candidate rows
    x2g: jax.Array,    # (n, C) cached bf16 squared norms (0 invalid)
    ids: jax.Array,    # (n, C) candidate node ids, -1 = invalid slot
    cn: int,
) -> tuple[jax.Array, jax.Array]:
    """bf16 local-join pair-distance tensor: the fp32 oracle applied to
    bf16-rounded rows (see knn_search_dists_bf16). Oracle for
    knn_join_dists_bf16_blocked."""
    return knn_join_dists(xg, x2g, ids, cn)


# ---------------------------------------------------------------------------
# Bounded top-k neighbor-list merge (paper §2 "calculate and update")
# ---------------------------------------------------------------------------

def knn_merge(
    cur_dist: jax.Array,   # (n, k) ascending
    cur_idx: jax.Array,    # (n, k)
    cand_dist: jax.Array,  # (n, c)
    cand_idx: jax.Array,   # (n, c)  (-1 = invalid slot)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Merge candidates into sorted k-NN lists, deduplicating by id.

    Returns (new_dist, new_idx, updated) where ``updated`` is the per-row
    count of accepted candidates (the NN-Descent convergence counter).
    """
    n, k = cur_dist.shape
    # Invalidate candidates that already sit in the row's neighbor list or
    # that duplicate an earlier candidate in the same row.
    dup_graph = (cand_idx[:, :, None] == cur_idx[:, None, :]).any(-1)
    c = cand_idx.shape[1]
    dup_self = jnp.zeros_like(dup_graph)
    eq = cand_idx[:, :, None] == cand_idx[:, None, :]
    earlier = jnp.tril(jnp.ones((c, c), dtype=bool), k=-1)[None]
    dup_self = (eq & earlier).any(-1)
    invalid = dup_graph | dup_self | (cand_idx < 0)
    cand_dist = jnp.where(invalid, jnp.inf, cand_dist)

    all_dist = jnp.concatenate([cur_dist, cand_dist], axis=1)
    all_idx = jnp.concatenate([cur_idx, cand_idx], axis=1)
    order = jnp.argsort(all_dist, axis=1, stable=True)
    new_dist = jnp.take_along_axis(all_dist, order[:, :k], axis=1)
    new_idx = jnp.take_along_axis(all_idx, order[:, :k], axis=1)
    # a candidate was accepted iff it landed in the first k slots
    accepted = order[:, :k] >= k
    updated = jnp.sum(accepted & jnp.isfinite(new_dist), axis=1)
    return new_dist, new_idx, updated


def knn_pool_insert(
    pool_dist: jax.Array,  # (n, p) ascending, +inf = empty
    pool_idx: jax.Array,   # (n, p), -1 = empty
    pool_new: jax.Array,   # (n, p) bool
    cand_dist: jax.Array,  # (n, w)
    cand_idx: jax.Array,   # (n, w)  (-1 = invalid slot)
) -> tuple[jax.Array, ...]:
    """The search's pool merge with its ``new`` flags: ``knn_merge``, then
    surviving entries keep their flag and accepted ones are new. Returns
    (dist, idx, new, inserted, offered), ``offered`` the per-row count of
    candidates left after the dedup. Oracle for knn_pool_insert_blocked."""
    dist, idx, inserted = knn_merge(pool_dist, pool_idx, cand_dist, cand_idx)
    hit = idx[:, :, None] == pool_idx[:, None, :]
    was_old = hit.any(-1)
    kept_new = (hit & pool_new[:, None, :]).any(-1)
    new = jnp.where(was_old, kept_new, True) & (idx >= 0)
    dup = (cand_idx[:, :, None] == pool_idx[:, None, :]).any(-1)
    w = cand_idx.shape[1]
    earlier = jnp.tril(jnp.ones((w, w), dtype=bool), k=-1)[None]
    dup |= ((cand_idx[:, :, None] == cand_idx[:, None, :]) & earlier).any(-1)
    offered = jnp.sum((cand_idx >= 0) & ~dup, axis=1).astype(jnp.int32)
    return dist, idx, new, inserted.astype(jnp.int32), offered


def knn_compact(
    cur_dist: jax.Array,   # (n, k) ascending, +inf = empty
    cur_idx: jax.Array,    # (n, k), -1 = empty
    drop: jax.Array,       # (n, k) bool — entries to remove
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Drop masked entries from sorted lists; survivors stay sorted and
    packed to the front, freed slots become (inf, -1). Returns
    (dist, idx, removed). Oracle for knn_compact_blocked."""
    n, k = cur_dist.shape
    valid = cur_idx >= 0
    removed = jnp.sum(drop & valid, axis=1).astype(jnp.int32)
    masked = jnp.where(drop | ~valid, jnp.inf, cur_dist)
    order = jnp.argsort(masked, axis=1, stable=True)
    new_dist = jnp.take_along_axis(masked, order, axis=1)
    new_idx = jnp.where(
        jnp.isfinite(new_dist),
        jnp.take_along_axis(cur_idx, order, axis=1),
        -1,
    )
    return new_dist, new_idx, removed


# ---------------------------------------------------------------------------
# Frontier (gather/scatter) row dispatch — the online subsystem's chunked
# update primitives: apply merge/compact to an explicit compacted set of
# row ids instead of the whole store, so update cost scales with the
# frontier size (core/online.py). ``rows`` is a padded id buffer (-1 =
# padding slot, ids must be unique); non-listed rows pass through.
# ---------------------------------------------------------------------------

def knn_merge_rows(
    cur_dist: jax.Array,   # (n, k) ascending
    cur_idx: jax.Array,    # (n, k)
    rows: jax.Array,       # (f,) unique row ids, -1 = padding
    cand_dist: jax.Array,  # (f, c)
    cand_idx: jax.Array,   # (f, c)  (-1 = invalid slot)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Merge per-frontier-row candidates into the listed rows only.

    Returns (dist, idx, updated) with full (n, k) arrays — rows not in
    ``rows`` are untouched — and ``updated`` (f,) the per-frontier-row
    accepted count (0 on padding slots). Oracle for knn_merge_rows_blocked.
    """
    n, _ = cur_dist.shape
    ok = rows >= 0
    safe = jnp.where(ok, rows, 0)
    sub_d = cur_dist[safe]
    sub_i = cur_idx[safe]
    cand_idx = jnp.where(ok[:, None], cand_idx, -1)
    md, mi, upd = knn_merge(sub_d, sub_i, cand_dist, cand_idx)
    tgt = jnp.where(ok, rows, n)          # padding scatters out of bounds
    out_d = cur_dist.at[tgt].set(md, mode="drop")
    out_i = cur_idx.at[tgt].set(mi, mode="drop")
    return out_d, out_i, jnp.where(ok, upd, 0)


def knn_compact_rows(
    cur_dist: jax.Array,   # (n, k) ascending, +inf = empty
    cur_idx: jax.Array,    # (n, k), -1 = empty
    rows: jax.Array,       # (f,) unique row ids, -1 = padding
    drop: jax.Array,       # (f, k) bool — entries to remove, frontier-local
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Drop masked entries from the listed rows only.

    Returns (dist, idx, removed) with full (n, k) arrays and ``removed``
    (f,) per-frontier-row. Oracle for knn_compact_rows_blocked."""
    n, _ = cur_dist.shape
    ok = rows >= 0
    safe = jnp.where(ok, rows, 0)
    sub_d = cur_dist[safe]
    sub_i = cur_idx[safe]
    drop = drop & ok[:, None]
    cd, ci, removed = knn_compact(sub_d, sub_i, drop)
    tgt = jnp.where(ok, rows, n)
    out_d = cur_dist.at[tgt].set(cd, mode="drop")
    out_i = cur_idx.at[tgt].set(ci, mode="drop")
    return out_d, out_i, jnp.where(ok, removed, 0)


# ---------------------------------------------------------------------------
# Flash attention (blocked attention for the LM stack)
# ---------------------------------------------------------------------------

def attention(
    q: jax.Array,              # (B, Lq, H, Dh)
    k: jax.Array,              # (B, Lk, Hkv, Dh)
    v: jax.Array,              # (B, Lk, Hkv, Dh)
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    q_offset: int = 0,
) -> jax.Array:
    """Reference multi-head attention with GQA, sliding window, softcap.

    q_offset: absolute position of q[0] (for decode: q_offset = cache_len).
    """
    B, Lq, H, Dh = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / jnp.sqrt(Dh).astype(jnp.float32)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), kr.astype(jnp.float32)
    ) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(Lq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((Lq, k.shape[1]), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vr.astype(jnp.float32))
    return out.astype(q.dtype)
