"""Bounded neighbor-list merge kernel (paper §2 "calculate and update").

NN-Descent keeps, per node, a sorted bounded list of its k current nearest
neighbors. Each iteration produces a batch of candidate (id, distance)
pairs per node which must be merged into that list with deduplication.

The paper does this with scalar sorted-array insertion; the TPU form is a
row-blocked kernel: TM rows are processed per grid step, and the merge is a
k-step vectorized selection (each step extracts the row-wise minimum of the
remaining pool of current-neighbors + candidates). k is small (20 in all
paper experiments) so the unrolled k x (k + c) compare network stays in
VREGs — the analog of the paper keeping its 25 accumulators in registers.

Outputs the merged sorted lists and the per-row accepted-candidate count
(the convergence counter c in the NN-Descent stopping rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


_BIG = float(jnp.finfo(jnp.float32).max)


def block_rows(n: int, k: int, c: int) -> int:
    """Rows per block of the merge/compact kernels, from VMEM bytes: the
    double-buffered (k,) list rows, (c,) candidate rows and outputs, plus
    about eight (k + c,) temporaries of the selection network."""
    io = 5 * tiling.row_bytes(k, jnp.float32) + 2 * tiling.row_bytes(
        c, jnp.float32) + tiling.row_bytes(1, jnp.int32)
    temps = 8 * tiling.row_bytes(k + c, jnp.float32)
    return tiling.rows_per_block(2 * io + temps, n)


def select_ascending(pool, ids, steps: int, flag=None):
    """The ``steps`` smallest entries of each row of ``pool`` (TM, P), in
    ascending order, ties to the lowest position — the min-extraction
    network every selection kernel of this package runs. Each step takes
    the row minimum, its first position (a min over the lane iota, no
    gather), and masks it out. A ``fori_loop`` with 2-D carries keeps
    the VMEM footprint independent of ``steps``.

    Returns (dist, ids, flag) of the picks, each (TM, steps): raw
    minima (``_BIG`` once a row runs dry), the ``ids`` payload at the
    pick, and the int32 ``flag`` payload at the pick (0 without one, and
    0 for a position picked before, so a dry row reports no flag)."""
    tm, p = pool.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (tm, p), 1)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (tm, steps), 1)

    def step(t, carry):
        pool, flag, od, oi, of = carry
        dmin = jnp.min(pool, axis=1, keepdims=True)
        pos = jnp.min(jnp.where(pool == dmin, lane, p), axis=1,
                      keepdims=True)
        onehot = lane == pos
        at = out_lane == t
        od = jnp.where(at, dmin, od)
        oi = jnp.where(at, jnp.sum(jnp.where(onehot, ids, 0), axis=1,
                                   keepdims=True), oi)
        if flag is not None:
            of = jnp.where(at, jnp.sum(jnp.where(onehot, flag, 0), axis=1,
                                       keepdims=True), of)
            flag = jnp.where(onehot, 0, flag)
        return jnp.where(onehot, _BIG, pool), flag, od, oi, of

    init = (pool, flag, jnp.full((tm, steps), _BIG, jnp.float32),
            jnp.zeros((tm, steps), jnp.int32),
            jnp.zeros((tm, steps), jnp.int32))
    _, _, od, oi, of = jax.lax.fori_loop(0, steps, step, init)
    return od, oi, of


def _merge_kernel(cd_ref, ci_ref, qd_ref, qi_ref, od_ref, oi_ref, upd_ref, *, k: int):
    cur_d = cd_ref[...]          # (TM, K) ascending
    cur_i = ci_ref[...]          # (TM, K)
    cand_d = qd_ref[...]         # (TM, C)
    cand_i = qi_ref[...]         # (TM, C)

    # --- dedup: candidate already in list, duplicate of an earlier
    # candidate, or invalid. Column-at-a-time compares in fori_loops keep
    # every mask 2-D (Mosaic lowers no (TM, C, C) boolean tensor) and the
    # VMEM footprint independent of k and C; column j is taken by a
    # one-hot lane sum
    c = cand_d.shape[1]
    cur_lane = jax.lax.broadcasted_iota(jnp.int32, cur_i.shape, 1)
    cand_lane = jax.lax.broadcasted_iota(jnp.int32, cand_i.shape, 1)

    def column(a, lane, j):
        return jnp.sum(jnp.where(lane == j, a, 0), axis=1, keepdims=True)

    def mark(hit):      # loop carries stay int32: Mosaic carries no i1
        return hit.astype(jnp.int32)

    dup = jax.lax.fori_loop(
        0, k, lambda j, dup: dup | mark(
            cand_i == column(cur_i, cur_lane, j)),
        mark(cand_i < 0))
    dup = jax.lax.fori_loop(
        0, c - 1, lambda j, dup: dup | mark(
            (cand_i == column(cand_i, cand_lane, j)) & (cand_lane > j)),
        dup)
    cand_d = jnp.where(dup > 0, _BIG, cand_d)

    # --- k-step min-extraction over the current list + candidates
    pool_d = jnp.concatenate(
        [jnp.where(jnp.isinf(cur_d), _BIG, cur_d), cand_d], axis=1)
    pool_i = jnp.concatenate([cur_i, cand_i], axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, pool_d.shape, 1)
    d, i, took_cand = select_ascending(pool_d, pool_i, k,
                                       (lane >= k).astype(jnp.int32))
    hit = d < _BIG
    od_ref[...] = jnp.where(hit, d, jnp.inf)
    oi_ref[...] = jnp.where(hit, i, -1)
    upd_ref[...] = jnp.sum((hit & (took_cand > 0)).astype(jnp.int32),
                           axis=1, keepdims=True)


def _compact_kernel(cd_ref, ci_ref, dr_ref, od_ref, oi_ref, rm_ref, *, k: int):
    """Drop masked entries from sorted rows, keeping the survivors sorted
    and packed to the front — the tombstone-purge primitive of the online
    subsystem (core/online.py). Same min-extraction network as
    ``_merge_kernel``: no gathers, VPU-native."""
    cur_d = cd_ref[...]                 # (TM, K) ascending
    cur_i = ci_ref[...]                 # (TM, K)
    drop = dr_ref[...] != 0             # (TM, K) int32 mask -> bool

    valid = cur_i >= 0
    rm_ref[...] = jnp.sum(
        (drop & valid).astype(jnp.int32), axis=1, keepdims=True
    )
    # survivors are tracked by mask, not by distance magnitude, so valid
    # entries at placeholder distances (heap.init_random's 3e38) survive
    # exactly as in the ref.knn_compact oracle
    keep = ~drop & valid & jnp.isfinite(cur_d)
    d, i, real = select_ascending(jnp.where(keep, cur_d, _BIG), cur_i, k,
                                  keep.astype(jnp.int32))
    od_ref[...] = jnp.where(real > 0, d, jnp.inf)
    oi_ref[...] = jnp.where(real > 0, i, -1)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def knn_compact_blocked(
    cur_dist: jax.Array,   # (n, k) ascending, +inf = empty slot
    cur_idx: jax.Array,    # (n, k) int32, -1 = empty
    drop: jax.Array,       # (n, k) bool — entries to remove
    *,
    tm: int | None = None,    # rows per block (None: from VMEM bytes)
    interpret: bool = False,
):
    """Remove ``drop``-masked entries from sorted bounded lists.

    Returns (dist, idx, removed): survivors packed to the front in
    ascending order, freed slots set to (inf, -1), ``removed`` the per-row
    count of dropped valid entries.
    """
    n, k = cur_dist.shape
    tm = tm or block_rows(n, k, k)
    npad = ((n + tm - 1) // tm) * tm
    pad = npad - n
    cur_dist = jnp.pad(cur_dist, ((0, pad), (0, 0)), constant_values=jnp.inf)
    cur_idx = jnp.pad(cur_idx, ((0, pad), (0, 0)), constant_values=-1)
    drop_i = jnp.pad(
        drop.astype(jnp.int32), ((0, pad), (0, 0)), constant_values=0
    )

    kern = functools.partial(_compact_kernel, k=k)
    od, oi, rm = pl.pallas_call(
        kern,
        grid=(npad // tm,),
        in_specs=[
            pl.BlockSpec((tm, k), lambda i: (i, 0)),
            pl.BlockSpec((tm, k), lambda i: (i, 0)),
            pl.BlockSpec((tm, k), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tm, k), lambda i: (i, 0)),
            pl.BlockSpec((tm, k), lambda i: (i, 0)),
            pl.BlockSpec((tm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad, k), jnp.float32),
            jax.ShapeDtypeStruct((npad, k), jnp.int32),
            jax.ShapeDtypeStruct((npad, 1), jnp.int32),
        ],
        interpret=interpret,
        name="knn_compact",
    )(cur_dist, cur_idx, drop_i)
    return od[:n], oi[:n], rm[:n, 0]


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def knn_merge_blocked(
    cur_dist: jax.Array,   # (n, k) ascending, +inf = empty slot
    cur_idx: jax.Array,    # (n, k) int32, -1 = empty
    cand_dist: jax.Array,  # (n, c) f32
    cand_idx: jax.Array,   # (n, c) int32, -1 = invalid
    *,
    tm: int | None = None,    # rows per block (None: from VMEM bytes)
    interpret: bool = False,
):
    n, k = cur_dist.shape
    c = cand_dist.shape[1]
    tm = tm or block_rows(n, k, c)
    npad = ((n + tm - 1) // tm) * tm
    pad = npad - n
    cur_dist = jnp.pad(cur_dist, ((0, pad), (0, 0)), constant_values=jnp.inf)
    cur_idx = jnp.pad(cur_idx, ((0, pad), (0, 0)), constant_values=-1)
    cand_dist = jnp.pad(cand_dist, ((0, pad), (0, 0)), constant_values=jnp.inf)
    cand_idx = jnp.pad(cand_idx, ((0, pad), (0, 0)), constant_values=-1)

    kern = functools.partial(_merge_kernel, k=k)
    od, oi, upd = pl.pallas_call(
        kern,
        grid=(npad // tm,),
        in_specs=[
            pl.BlockSpec((tm, k), lambda i: (i, 0)),
            pl.BlockSpec((tm, k), lambda i: (i, 0)),
            pl.BlockSpec((tm, c), lambda i: (i, 0)),
            pl.BlockSpec((tm, c), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tm, k), lambda i: (i, 0)),
            pl.BlockSpec((tm, k), lambda i: (i, 0)),
            pl.BlockSpec((tm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad, k), jnp.float32),
            jax.ShapeDtypeStruct((npad, k), jnp.int32),
            jax.ShapeDtypeStruct((npad, 1), jnp.int32),
        ],
        interpret=interpret,
        name="knn_merge",
    )(cur_dist, cur_idx, cand_dist, cand_idx)
    return od[:n], oi[:n], upd[:n, 0]


# ---------------------------------------------------------------------------
# Frontier (gather/scatter) chunked dispatch — the online subsystem's
# sparse-update entry points: gather a compacted padded buffer of row ids,
# run the same row-blocked kernels over the (f, ...) chunk (the pallas grid
# is the per-chunk tiling), scatter the results back. Cost scales with the
# frontier size f, not the store size n. Oracles: ref.knn_merge_rows /
# ref.knn_compact_rows.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def knn_merge_rows_blocked(
    cur_dist: jax.Array,   # (n, k) ascending, +inf = empty slot
    cur_idx: jax.Array,    # (n, k) int32, -1 = empty
    rows: jax.Array,       # (f,) unique row ids, -1 = padding
    cand_dist: jax.Array,  # (f, c) f32
    cand_idx: jax.Array,   # (f, c) int32, -1 = invalid
    *,
    tm: int | None = None,    # rows per block (None: from VMEM bytes)
    interpret: bool = False,
):
    """Merge candidates into the listed rows only (full arrays returned)."""
    n, _ = cur_dist.shape
    ok = rows >= 0
    safe = jnp.where(ok, rows, 0)
    sub_d = cur_dist[safe]
    sub_i = cur_idx[safe]
    cand_idx = jnp.where(ok[:, None], cand_idx, -1)
    md, mi, upd = knn_merge_blocked(
        sub_d, sub_i, cand_dist, cand_idx, tm=tm, interpret=interpret
    )
    tgt = jnp.where(ok, rows, n)
    out_d = cur_dist.at[tgt].set(md, mode="drop")
    out_i = cur_idx.at[tgt].set(mi, mode="drop")
    return out_d, out_i, jnp.where(ok, upd, 0)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def knn_compact_rows_blocked(
    cur_dist: jax.Array,   # (n, k) ascending, +inf = empty slot
    cur_idx: jax.Array,    # (n, k) int32, -1 = empty
    rows: jax.Array,       # (f,) unique row ids, -1 = padding
    drop: jax.Array,       # (f, k) bool — frontier-local entries to remove
    *,
    tm: int | None = None,    # rows per block (None: from VMEM bytes)
    interpret: bool = False,
):
    """Drop masked entries from the listed rows only (full arrays returned)."""
    n, _ = cur_dist.shape
    ok = rows >= 0
    safe = jnp.where(ok, rows, 0)
    sub_d = cur_dist[safe]
    sub_i = cur_idx[safe]
    drop = drop & ok[:, None]
    cd, ci, removed = knn_compact_blocked(
        sub_d, sub_i, drop, tm=tm, interpret=interpret
    )
    tgt = jnp.where(ok, rows, n)
    out_d = cur_dist.at[tgt].set(cd, mode="drop")
    out_i = cur_idx.at[tgt].set(ci, mode="drop")
    return out_d, out_i, jnp.where(ok, removed, 0)
