"""Fused serving-search kernel (paper §3.3 blocked evaluation applied at
QUERY time) — the distance tile of the batched multi-expansion beam search.

The seed ``graph_search`` expanded ONE pool node per query per round and
evaluated its k neighbor distances with unblocked scalar row gathers plus a
per-round recomputation of the query norm. The fused search
(core/graph_search.py) instead expands the top-E unexpanded pool nodes of a
whole *block* of queries at once; the E·k gathered candidate rows per query
form a (q_block, E·k, dp) feature tile, and this kernel turns that tile
into the (q_block, E·k) candidate distance tile in one MXU pass:

    d(q, c) = ||q||^2 + ||c||^2 - 2 q·c

with both norms precomputed ONCE per batch (hoisted out of the round loop)
and the validity mask (invalid / dead candidates arrive as id -1) folded
into the epilogue: masked candidates come out +inf so the downstream
``knn_join_select`` top-C selection and bounded pool merge drop them for
free. The restriction to l2 is what makes this blocked form possible — the
source paper's core lesson, applied to the serving path.

That restriction is NOT a metric restriction: cosine and MIPS serving
(core/metric.py) reduce to squared l2 by transforming the INPUTS (rows
normalized; the MIPS augmented coordinate appended), so this kernel — and
every other kernel in the package — runs those metrics unchanged, with
identical tiles, masks and epilogues. Filtered queries ride the same id
mask: a row filtered out by a predicate reaches this kernel as id -1,
exactly like a tombstoned or padded candidate, and exits as +inf — zero
per-metric or per-filter kernel variants to maintain.

The gather itself (adjacency rows -> candidate ids -> feature rows) stays
outside the kernel in XLA, like every other kernel in this package
(cf. knn_join_dists_blocked's pre-gathered ``xg``): Pallas sees only
dense, layout-native tiles. ref.py holds the pure-jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def search_block_rows(n: int, w: int, dp: int, dtype) -> int:
    """Query rows per block of a search-distance kernel, from its VMEM
    bytes: the double-buffered (W, dp) candidate tile, query row and
    (W,) vectors, plus two fp32 (W, dp) temporaries (the operand cast to
    fp32 and the batched product)."""
    io = (tiling.tile_bytes((w, dp), dtype) + tiling.row_bytes(dp, dtype)
          + 3 * tiling.row_bytes(1, jnp.float32)
          + 4 * tiling.row_bytes(w, jnp.float32))
    temps = 2 * tiling.tile_bytes((w, dp), jnp.float32)
    return tiling.rows_per_block(2 * io + temps, n, cap=128)


def search_tile(q, q2, cg, c2, ids, scale=None):
    """Masked sq-l2 of one query block against its gathered candidates:
    (TQ, dp) x (TQ, W, dp) -> (TQ, W), shared by the fp32, bf16 and int8
    kernels. Operands are cast to fp32 first — exact for bf16 and int8
    values, and Mosaic contracts a batched (TQ, W, dp) x (TQ, dp) pair
    only in 32-bit. fp32 operands contract at ``Precision.HIGHEST``
    (Mosaic's default rounds them to bf16 passes); bf16 and int8 values
    are exact in one pass. ``scale`` (TQ, W) multiplies the cross terms
    (the int8 dequant scales)."""
    exact = cg.dtype == jnp.float32
    ab = jax.lax.dot_general(
        cg.astype(jnp.float32), q.astype(jnp.float32),
        (((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32,
    )                                         # (TQ, W)
    if scale is not None:
        ab = scale * ab
    dd = q2 + c2 - 2.0 * ab
    return jnp.where(ids >= 0, jnp.maximum(dd, 0.0), jnp.inf)


def _search_dists_kernel(q_ref, q2_ref, cg_ref, c2_ref, ids_ref, od_ref):
    """Candidate distance tile for one query block: (TQ, dp) queries x
    (TQ, W, dp) gathered candidate features -> (TQ, W) masked sq-l2."""
    od_ref[...] = search_tile(q_ref[...], q2_ref[...], cg_ref[...],
                              c2_ref[...], ids_ref[...])


@functools.partial(jax.jit, static_argnames=("tq", "interpret"))
def knn_search_dists_blocked(
    q: jax.Array,      # (nq, dp) query block features
    q2: jax.Array,     # (nq,) hoisted query squared norms
    cg: jax.Array,     # (nq, W, dp) gathered candidate features
    c2g: jax.Array,    # (nq, W) cached candidate squared norms
    ids: jax.Array,    # (nq, W) candidate ids, -1 = invalid (incl. dead)
    *,
    tq: int | None = None,   # query rows per block (None: VMEM bytes)
    interpret: bool = False,
):
    """Blocked query-time candidate distances.

    Returns dists (nq, W) f32 with +inf on invalid candidates. Validity
    (including tombstone/alive masking) is encoded by the caller as
    ``ids == -1`` and applied in the kernel epilogue.
    """
    return search_call(_search_dists_kernel, "knn_search_dists", [q, q2],
                       cg, [c2g], ids, tq, interpret)


def search_call(kernel, name, q_rows, cg, c_rows, ids, tq, interpret):
    """pallas_call of a search-distance kernel (fp32 here, bf16/int8 in
    kernels/l2_quant.py): ``q_rows`` are the (nq, dp) query rows then
    (nq,) per-query vectors, ``cg`` the (nq, W, dp) gathered candidates,
    ``c_rows`` (nq, W) per-candidate vectors, ``ids`` (nq, W)."""
    nq, w, dp = cg.shape
    tq = tq or search_block_rows(nq, w, dp, cg.dtype)
    npad = ((nq + tq - 1) // tq) * tq
    pad = npad - nq
    q, *qv = q_rows
    q = jnp.pad(q, ((0, pad), (0, 0)))
    qv = [jnp.pad(v, (0, pad))[:, None] for v in qv]
    cg = jnp.pad(cg, ((0, pad), (0, 0), (0, 0)))
    c_rows = [jnp.pad(v, ((0, pad), (0, 0))) for v in c_rows]
    ids = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
    col = pl.BlockSpec((tq, 1), lambda i: (i, 0))
    row = pl.BlockSpec((tq, w), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(npad // tq,),
        in_specs=[pl.BlockSpec((tq, dp), lambda i: (i, 0))]
        + [col] * len(qv)
        + [pl.BlockSpec((tq, w, dp), lambda i: (i, 0, 0))]
        + [row] * (len(c_rows) + 1),
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((npad, w), jnp.float32),
        interpret=interpret,
        name=name,
    )(q, *qv, cg, *c_rows, ids)[:nq]
