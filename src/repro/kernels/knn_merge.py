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

The graph search's round merge runs a narrower form, ``knn_pool_insert``:
its pool is the beam (256 wide) and a round offers only E·k candidates,
so it inserts candidate by candidate into the sorted pool, each a
vectorized step over the pool's lanes, instead of extracting beam minima
from pool plus candidates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
# Search pool insert: the graph search's per-round merge, sized by the
# round's candidates instead of the beam (core/graph_search.py).
# ---------------------------------------------------------------------------


def pool_insert_rows(n: int, p: int, w: int) -> int:
    """Rows per block of the pool-insert kernel, from VMEM bytes: the
    double-buffered pool rows in and out (dist, idx, flag), the candidate
    rows and the two counts, plus about a dozen (p,) temporaries of the
    insertion step. At most 64 rows, so that each (rows, p) array of the
    step spans few vregs (not yet tuned on a chip)."""
    io = 6 * tiling.row_bytes(p, jnp.float32) + 2 * tiling.row_bytes(
        w, jnp.float32) + 2 * tiling.row_bytes(1, jnp.int32)
    temps = 12 * tiling.row_bytes(p, jnp.float32) + 4 * tiling.row_bytes(
        w, jnp.float32)
    return tiling.rows_per_block(2 * io + temps, n, cap=64)


def _pool_insert_kernel(nc_ref, pd_ref, pi_ref, pn_ref, cd_ref, ci_ref,
                        od_ref, oi_ref, on_ref, ins_ref, off_ref):
    """Insert a row block's candidates into its sorted pools, one
    candidate column per step. A candidate goes after every pool entry
    at a distance ``<=`` its own (the pool wins ties), the tail shifts one
    lane (``pltpu.roll``) and the last lane falls off: the stable merge of
    the pool and the candidates, cut to the pool's width. A candidate is
    dropped when its id is in the pool as it came in, or repeats an
    earlier candidate. ``nc_ref`` holds, per block, one past the last
    column that holds a valid candidate in any of its rows."""
    pool_i = pi_ref[...]                      # (TM, P) the pool as it came
    cand_d = cd_ref[...]                      # (TM, W)
    tm, p = pool_i.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (tm, p), 1)
    cand_lane = jax.lax.broadcasted_iota(jnp.int32, cand_d.shape, 1)

    def column(a, j):
        return jnp.sum(jnp.where(cand_lane == j, a, 0), axis=1,
                       keepdims=True)

    def step(j, carry):
        d, i, flag, cand_i, offered = carry
        cj = column(cand_i, j)                                  # (TM, 1)
        in_pool = jnp.sum(jnp.where(pool_i == cj, 1, 0), axis=1,
                          keepdims=True)
        ok = (cj >= 0) & (in_pool == 0)
        # later copies of this id are dropped where they stand
        cand_i = jnp.where((cand_i == cj) & (cand_lane > j), -1, cand_i)
        dj = jnp.where(ok, column(cand_d, j), _BIG)
        # a prefix of the lanes; every lane when the candidate is dropped
        keep = (d <= dj).astype(jnp.int32)
        at = (keep == 0) & ((pltpu.roll(keep, 1, 1) == 1) | (lane == 0))
        keep = keep == 1
        d = jnp.where(keep, d, jnp.where(at, dj, pltpu.roll(d, 1, 1)))
        i = jnp.where(keep, i, jnp.where(at, cj, pltpu.roll(i, 1, 1)))
        # flag 2 marks an inserted entry: new, and counted at the end
        flag = jnp.where(keep, flag,
                         jnp.where(at, 2, pltpu.roll(flag, 1, 1)))
        return d, i, flag, cand_i, offered + ok.astype(jnp.int32)

    d0 = pd_ref[...]
    init = (jnp.where(jnp.isinf(d0), _BIG, d0), pool_i, pn_ref[...],
            ci_ref[...], jnp.zeros((tm, 1), jnp.int32))
    d, i, flag, _, offered = jax.lax.fori_loop(
        0, nc_ref[pl.program_id(0)], step, init)
    hit = d < _BIG
    od_ref[...] = jnp.where(hit, d, jnp.inf)
    oi_ref[...] = jnp.where(hit, i, -1)
    on_ref[...] = jnp.where(hit & (flag > 0), 1, 0)
    ins_ref[...] = jnp.sum(jnp.where(flag == 2, 1, 0), axis=1, keepdims=True)
    off_ref[...] = offered


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def knn_pool_insert_blocked(
    pool_dist: jax.Array,  # (n, p) ascending, +inf = empty slot
    pool_idx: jax.Array,   # (n, p) int32, -1 = empty
    pool_new: jax.Array,   # (n, p) int32 flags, 0 on empty slots
    cand_dist: jax.Array,  # (n, w) f32
    cand_idx: jax.Array,   # (n, w) int32, -1 = invalid
    *,
    tm: int | None = None,    # rows per block (None: from VMEM bytes)
    interpret: bool = False,
):
    """Merge candidates into sorted pools with their ``new`` flags: the
    result equals the stable sort of each pool followed by its deduped
    candidates, cut to ``p`` (``ref.knn_pool_insert``). Inserted entries
    come back flagged 1; surviving pool entries keep their flag. The work
    is O(p) per candidate column up to the block's last valid one, not
    O(p + w) per pool slot: the search hands in ``w`` = E·k candidates,
    sorted and packed to the front by ``knn_join_select``.

    Returns (dist (n, p), idx (n, p), new (n, p) int32, inserted (n,),
    offered (n,)): ``inserted`` counts the candidates that are in the
    returned pool, ``offered`` those that survived the dedup."""
    n, p = pool_dist.shape
    w = cand_dist.shape[1]
    tm = tm or pool_insert_rows(n, p, w)
    npad = ((n + tm - 1) // tm) * tm
    pad = npad - n
    pool_dist = jnp.pad(pool_dist, ((0, pad), (0, 0)),
                        constant_values=jnp.inf)
    pool_idx = jnp.pad(pool_idx, ((0, pad), (0, 0)), constant_values=-1)
    pool_new = jnp.pad(pool_new, ((0, pad), (0, 0)))
    cand_dist = jnp.pad(cand_dist, ((0, pad), (0, 0)),
                        constant_values=jnp.inf)
    cand_idx = jnp.pad(cand_idx, ((0, pad), (0, 0)), constant_values=-1)
    # per block: one past the last column with a valid candidate in it
    col = jax.lax.broadcasted_iota(jnp.int32, cand_idx.shape, 1)
    ncols = jnp.max(jnp.where(cand_idx >= 0, col + 1, 0).reshape(
        npad // tm, tm * w), axis=1)

    rows = lambda b, nc: (b, 0)
    od, oi, on, ins, off = pl.pallas_call(
        _pool_insert_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(npad // tm,),
            in_specs=[pl.BlockSpec((tm, p), rows)] * 3
            + [pl.BlockSpec((tm, w), rows)] * 2,
            out_specs=[pl.BlockSpec((tm, p), rows)] * 3
            + [pl.BlockSpec((tm, 1), rows)] * 2,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((npad, p), jnp.float32),
            jax.ShapeDtypeStruct((npad, p), jnp.int32),
            jax.ShapeDtypeStruct((npad, p), jnp.int32),
            jax.ShapeDtypeStruct((npad, 1), jnp.int32),
            jax.ShapeDtypeStruct((npad, 1), jnp.int32),
        ],
        interpret=interpret,
        name="knn_pool_insert",
    )(ncols, pool_dist, pool_idx, pool_new, cand_dist, cand_idx)
    return od[:n], oi[:n], on[:n], ins[:n, 0], off[:n, 0]


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
