"""Fused local-join kernel family (paper §3.3 blocked evaluation fused with
§2 update routing) — the build hot path without the global pair sort.

NN-Descent's local join evaluates all new x new / new x old candidate pairs
per node and routes every evaluated pair to BOTH endpoints. The seed
implementation flattened the pairs into an O(n*C^2) (receiver, candidate,
dist) list and pushed it through a global ``jnp.lexsort`` before the merge
— the sort and its HBM round-trips dominated iteration time and dwarfed
the distance einsum (see benchmarks/bench_build.py).

The fused form keeps everything receiver-local, in two blocked kernels:

  * ``knn_join_dists_blocked`` — for a block of rows, the full candidate
    pair-distance tensor (C x C per row) is computed in VMEM via the
    norm-expansion MXU form, with the join validity mask (at least one
    endpoint "new", distinct slots, distinct ids, valid ids) folded into
    the epilogue: invalid pairs come out +inf, and the per-row count of
    valid unordered pairs (the paper's dist_evals counter) is emitted
    alongside.
  * ``knn_join_select_blocked`` — for a block of RECEIVER rows, the
    gathered incoming pair distances are prefiltered against the
    receiver's current k-th distance and reduced to the best ``c``
    (dist, idx) pairs by an in-kernel partial top-C (the same
    min-extraction selection network as kernels/knn_merge.py — VPU-native,
    no gathers). Output is O(rows * c) instead of O(rows * pairs).

Between the two kernels sits a single *incidence inversion* (one packed
single-key sort of the n*C candidate ids — ~30x fewer elements than the pair
list): each receiver learns which (row, slot) positions list it, gathers
its incoming distance rows from the pair tensor, and the select kernel
reduces them. Receivers are then contiguous rows, so the final merge is a
chunked block merge (core/heap.py ``merge_block``) with no sort at all.
The driver lives in core/nn_descent.py; ref.py holds the pure-jnp oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling
from repro.kernels.knn_merge import select_ascending


_BIG = float(jnp.finfo(jnp.float32).max)


def join_rows(cross, x2_ref, ids_ref, od_ref, ev_ref, *, cn: int,
              xs_ref=None):
    """Per-row body shared by the join-distance kernels (fp32 here, int8
    and bf16 in kernels/l2_quant.py): for each row r of the block,
    ``cross(r)`` gives the (C, C) fp32 cross terms of its candidates, and
    this adds the cached norms (scaled by the int8 dequant scales when
    ``xs_ref`` is given), applies the join validity mask and writes the
    row's distances and valid-pair count.

    Rows go through one 2-D matmul each: Mosaic has no batched
    (TB, C, dp) x (TB, C, dp) contraction. Per-row vectors arrive as
    (TB, 1, C) blocks so row r is a leading-axis index, and the (C, 1)
    column forms are taken with an identity mask (one non-zero term per
    sum, so they are exact)."""
    tb, _, c = ids_ref.shape
    slot_s = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    slot_t = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = slot_s == slot_t
    # join validity: at least one endpoint from the "new" pool (old x old
    # pairs are never evaluated — NN-Descent incremental search), distinct
    # slots; per row: both slots occupied, distinct node ids
    pair_ok = ((slot_s < cn) | (slot_t < cn)) & ~eye

    def col(v):
        return jnp.sum(jnp.where(eye, v, 0), axis=1, keepdims=True)

    def body(r, carry):
        x2r = x2_ref[r]                       # (1, C)
        idr = ids_ref[r]                      # (1, C), -1 = invalid
        ab = cross(r)                         # (C, C)
        if xs_ref is not None:
            xsr = xs_ref[r]
            ab = (col(xsr) * xsr) * ab
        dd = col(x2r) + x2r - 2.0 * ab
        idc = col(idr)
        ok = pair_ok & (idc >= 0) & (idr >= 0) & (idc != idr)
        od_ref[r] = jnp.where(ok, jnp.maximum(dd, 0.0), jnp.inf)
        cnt = jnp.sum(ok.astype(jnp.int32), axis=1, keepdims=True)
        # each unordered pair appears at (s, t) and (t, s)
        ev_ref[r] = jnp.sum(cnt, axis=0, keepdims=True) // 2
        return carry

    jax.lax.fori_loop(0, tb, body, 0)


def join_block_rows(n: int, c: int, dp: int, dtype, n_vec: int) -> int:
    """Rows per block of a join-distance kernel, from its VMEM bytes: the
    double-buffered (C, dp) feature tile, ``n_vec`` (1, C) per-row
    vectors, the (C, C) distance tile and the (1, 1) count."""
    row = (tiling.tile_bytes((c, dp), dtype)
           + n_vec * tiling.tile_bytes((1, c), jnp.float32)
           + tiling.tile_bytes((c, c), jnp.float32)
           + tiling.tile_bytes((1, 1), jnp.int32))
    return tiling.rows_per_block(2 * row, n, cap=128)


def join_call(kernel, name: str, feats, rows, ids, *, cn: int, tb,
              interpret: bool):
    """pallas_call of a join-distance kernel: ``feats`` (n, C, dp)
    gathered candidate rows, ``rows`` the (n, C) per-candidate vectors
    (norms, and scales for int8), ``ids`` (n, C). Returns (dists
    (n, C, C), evals (n,))."""
    n, c, dp = feats.shape
    tb = tb or join_block_rows(n, c, dp, feats.dtype, len(rows) + 1)
    npad = ((n + tb - 1) // tb) * tb
    pad = npad - n
    feats = jnp.pad(feats, ((0, pad), (0, 0), (0, 0)))
    rows = [jnp.pad(v, ((0, pad), (0, 0)))[:, None, :] for v in rows]
    ids = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)[:, None, :]
    vec = pl.BlockSpec((tb, 1, c), lambda i: (i, 0, 0))
    od, ev = pl.pallas_call(
        functools.partial(kernel, cn=cn),
        grid=(npad // tb,),
        in_specs=[pl.BlockSpec((tb, c, dp), lambda i: (i, 0, 0))]
        + [vec] * (len(rows) + 1),
        out_specs=[
            pl.BlockSpec((tb, c, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad, c, c), jnp.float32),
            jax.ShapeDtypeStruct((npad, 1, 1), jnp.int32),
        ],
        interpret=interpret,
        name=name,
    )(feats, *rows, ids)
    return od[:n], ev[:n, 0, 0]


def _join_dists_kernel(xg_ref, x2_ref, ids_ref, od_ref, ev_ref, *, cn: int):
    """Pair-distance tensor for one row block: (TB, C, dp) gathered
    candidate features -> (TB, C, C) masked squared-l2 distances."""
    def cross(r):
        x = xg_ref[r].astype(jnp.float32)     # (C, dp)
        # cross terms on the MXU at full fp32 precision
        return jax.lax.dot_general(
            x, x, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    join_rows(cross, x2_ref, ids_ref, od_ref, ev_ref, cn=cn)


@functools.partial(jax.jit, static_argnames=("cn", "tb", "interpret"))
def knn_join_dists_blocked(
    xg: jax.Array,     # (n, C, dp) gathered candidate features
    x2g: jax.Array,    # (n, C) cached squared norms (0 on invalid slots)
    ids: jax.Array,    # (n, C) candidate node ids, -1 = invalid slot
    *,
    cn: int,           # width of the "new" candidate prefix
    tb: int | None = None,   # rows per block (None: from VMEM bytes)
    interpret: bool = False,
):
    """Blocked local-join pair distances.

    Returns (dists (n, C, C) f32 with +inf on invalid pairs, evals (n,)
    int32 — the per-row count of valid unordered pairs).
    """
    return join_call(_join_dists_kernel, "knn_join_dists", xg, [x2g], ids,
                     cn=cn, tb=tb, interpret=interpret)


def _join_select_kernel(gd_ref, gi_ref, kth_ref, od_ref, oi_ref, *, c: int):
    """Receiver-side prefilter + partial top-c selection for one block of
    receiver rows, by the min-extraction network of kernels/knn_merge.py
    (``select_ascending``) — every step stays VPU-native."""
    gi = gi_ref[...]                          # (TR, W)
    # receiver-side prefilter: only pairs beating the receiver's current
    # k-th distance can change its list (paper §2 "update" short-circuit)
    pool = jnp.where((gi >= 0) & (gd_ref[...] < kth_ref[...]),
                     gd_ref[...], _BIG)
    d, i, _ = select_ascending(pool, gi, c)
    hit = d < _BIG
    od_ref[...] = jnp.where(hit, d, jnp.inf)
    oi_ref[...] = jnp.where(hit, i, -1)


def select_block_rows(n: int, w: int, c: int) -> int:
    """Rows per block of the select kernel, from its VMEM bytes: the
    double-buffered (W,) distance and id rows, the threshold and the two
    (c,) outputs, plus about six (W,) 32-bit temporaries of the
    selection network (pool, lane iota, flag, one-hot, masked payloads)."""
    io = (2 * tiling.row_bytes(w, jnp.float32)
          + tiling.row_bytes(1, jnp.float32)
          + 2 * tiling.row_bytes(c, jnp.float32))
    temps = 6 * tiling.row_bytes(w, jnp.float32)
    return tiling.rows_per_block(2 * io + temps, n)


@functools.partial(jax.jit, static_argnames=("c", "tr", "interpret"))
def knn_join_select_blocked(
    gd: jax.Array,     # (n, W) gathered incoming pair distances (+inf pad)
    gi: jax.Array,     # (n, W) their candidate ids (-1 pad)
    kth: jax.Array,    # (n,) receiver k-th distance (prefilter threshold)
    *,
    c: int,            # output width (merge buffer size)
    tr: int | None = None,   # rows per block (None: from VMEM bytes)
    interpret: bool = False,
):
    """Per-receiver best-c selection with the k-th-distance prefilter.

    Returns (dist (n, c) ascending with +inf fill, idx (n, c) with -1
    fill). Ties keep the lowest input position (stable, like the oracle).
    """
    n, w = gd.shape
    tr = tr or select_block_rows(n, w, c)
    npad = ((n + tr - 1) // tr) * tr
    pad = npad - n
    gd = jnp.pad(gd, ((0, pad), (0, 0)), constant_values=jnp.inf)
    gi = jnp.pad(gi, ((0, pad), (0, 0)), constant_values=-1)
    kth = jnp.pad(kth, (0, pad))

    kern = functools.partial(_join_select_kernel, c=c)
    od, oi = pl.pallas_call(
        kern,
        grid=(npad // tr,),
        in_specs=[
            pl.BlockSpec((tr, w), lambda i: (i, 0)),
            pl.BlockSpec((tr, w), lambda i: (i, 0)),
            pl.BlockSpec((tr, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tr, c), lambda i: (i, 0)),
            pl.BlockSpec((tr, c), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad, c), jnp.float32),
            jax.ShapeDtypeStruct((npad, c), jnp.int32),
        ],
        interpret=interpret,
        name="knn_join_select",
    )(gd, gi, kth[:, None])
    return od[:n], oi[:n]
