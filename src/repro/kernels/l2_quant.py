"""Quantized blocked-distance kernels (paper §3.3 blocking at int8/bf16
density) — the candidate-SCORING stage of the two-stage distance path.

Shape-for-shape these are the mixed-precision twins of the fp32 tiles in
kernels/knn_search.py (serving: (TQ, W) candidate tile per query block)
and kernels/knn_join.py (build: (TB, C, C) pair tensor per row block).
What changes is the operand feed and the epilogue:

  * **int8** — rows arrive as symmetric per-row int8 with fp32 dequant
    scales (core/quantize.py), 1/4 the HBM bytes per row. The build's
    pair tensors run int8 x int8 on the MXU with int32 accumulation
    (`preferred_element_type=jnp.int32`, the native int8 systolic path);
    the serving tile widens the rows to fp32 in VMEM first (Mosaic
    contracts its batched (TQ, W, dp) x (TQ, dp) form only in 32-bit;
    integer products and their sums are exact in fp32 for dp <= 1040).
    The scale application is FUSED into the epilogue together with the
    norm expansion:

        d(a, b) = ||a||^2 + ||b||^2 - 2 * s_a * s_b * (a_i8 . b_i8)

    with ||.||^2 the cached norms of the QUANTIZED rows, so d(a, a) == 0
    exactly and near-identical rows cannot cancel below the clamp.

  * **bf16** — rows arrive as bf16 (no scales, half the bytes); the
    build's pair tensors feed the MXU directly, the serving tile widens
    them in VMEM like int8. Accumulation stays fp32.

Every output is fp32 with +inf on masked entries, exactly like the fp32
kernels, so the downstream select/merge machinery is unchanged — only
the scoring dtype moved. Returned distances never come from here: the
two-stage callers (core/graph_search.py, core/nn_descent.py) re-rank
surviving candidates in exact fp32 difference form before returning,
so quantization shows up as bounded candidate-recall noise, never as a
wrong distance.

ref.py holds pure-jnp oracles. They accumulate the int8 cross terms in
fp32 (the fast CPU path: integer products are exact in fp32 while the
running sum stays under 2^24, i.e. for dp <= 1040 — every shipped dim),
bit-identical to the kernels' int32 accumulation in that regime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.knn_join import join_call, join_rows
from repro.kernels.knn_search import search_call, search_tile


# ---------------------------------------------------------------------------
# serving tiles: (TQ, W) candidate distances per query block
# ---------------------------------------------------------------------------


def _search_dists_q8_kernel(qq_ref, qs_ref, q2_ref, cq_ref, cs_ref, c2_ref,
                            ids_ref, od_ref):
    """int8 candidate tile: (TQ, dp) int8 queries x (TQ, W, dp) int8
    gathered candidates -> (TQ, W) masked sq-l2, with the dequant scales
    applied to the (exact) integer cross terms in the epilogue."""
    od_ref[...] = search_tile(qq_ref[...], q2_ref[...], cq_ref[...],
                              c2_ref[...], ids_ref[...],
                              scale=qs_ref[...] * cs_ref[...])


def _search_dists_bf16_kernel(q_ref, q2_ref, cg_ref, c2_ref, ids_ref, od_ref):
    """bf16 candidate tile: bf16 rows in VMEM, fp32 accumulation."""
    od_ref[...] = search_tile(q_ref[...], q2_ref[...], cg_ref[...],
                              c2_ref[...], ids_ref[...])


@functools.partial(jax.jit, static_argnames=("tq", "interpret"))
def knn_search_dists_q8_blocked(
    qq: jax.Array,     # (nq, dp) int8 query rows
    qscale: jax.Array,  # (nq,) query dequant scales
    q2: jax.Array,     # (nq,) quantized-query squared norms
    cq: jax.Array,     # (nq, W, dp) int8 gathered candidate rows
    cscale: jax.Array,  # (nq, W) candidate dequant scales
    c2g: jax.Array,    # (nq, W) cached quantized-candidate squared norms
    ids: jax.Array,    # (nq, W) candidate ids, -1 = invalid (incl. dead)
    *,
    tq: int | None = None,   # query rows per block (None: VMEM bytes)
    interpret: bool = False,
):
    """Blocked int8 query-time candidate distances (see module docstring).
    Returns dists (nq, W) f32 with +inf on invalid candidates."""
    return search_call(_search_dists_q8_kernel, "knn_search_dists_q8",
                        [qq, qscale, q2], cq, [cscale, c2g], ids, tq,
                        interpret)


@functools.partial(jax.jit, static_argnames=("tq", "interpret"))
def knn_search_dists_bf16_blocked(
    q: jax.Array,      # (nq, dp) bf16 query rows
    q2: jax.Array,     # (nq,) bf16-rounded-query squared norms (f32)
    cg: jax.Array,     # (nq, W, dp) bf16 gathered candidate rows
    c2g: jax.Array,    # (nq, W) cached bf16-candidate squared norms
    ids: jax.Array,    # (nq, W) candidate ids, -1 = invalid (incl. dead)
    *,
    tq: int | None = None,   # query rows per block (None: VMEM bytes)
    interpret: bool = False,
):
    """Blocked bf16 query-time candidate distances. Same contract as
    knn_search_dists_q8_blocked minus the scales."""
    return search_call(_search_dists_bf16_kernel, "knn_search_dists_bf16",
                        [q, q2], cg, [c2g], ids, tq, interpret)


# ---------------------------------------------------------------------------
# build tiles: (TB, C, C) local-join pair tensors per row block
# ---------------------------------------------------------------------------


def _join_dists_q8_kernel(xq_ref, x2_ref, xs_ref, ids_ref, od_ref, ev_ref,
                          *, cn: int):
    """int8 pair tensor for one row block: (TB, C, dp) int8 gathered
    candidates -> (TB, C, C) masked sq-l2, int32 MXU accumulation."""
    def cross(r):
        x = xq_ref[r]                         # (C, dp) int8
        return jax.lax.dot_general(
            x, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)

    join_rows(cross, x2_ref, ids_ref, od_ref, ev_ref, cn=cn, xs_ref=xs_ref)


def _join_dists_bf16_kernel(xg_ref, x2_ref, ids_ref, od_ref, ev_ref,
                            *, cn: int):
    """bf16 pair tensor for one row block, fp32 accumulation."""
    def cross(r):
        x = xg_ref[r]                         # (C, dp) bf16
        return jax.lax.dot_general(
            x, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    join_rows(cross, x2_ref, ids_ref, od_ref, ev_ref, cn=cn)


@functools.partial(jax.jit, static_argnames=("cn", "tb", "interpret"))
def knn_join_dists_q8_blocked(
    xq: jax.Array,     # (n, C, dp) int8 gathered candidate rows
    xscale: jax.Array,  # (n, C) candidate dequant scales
    x2g: jax.Array,    # (n, C) cached quantized squared norms (0 invalid)
    ids: jax.Array,    # (n, C) candidate node ids, -1 = invalid slot
    *,
    cn: int,           # width of the "new" candidate prefix
    tb: int | None = None,   # rows per block (None: from VMEM bytes)
    interpret: bool = False,
):
    """Blocked int8 local-join pair distances. Returns (dists (n, C, C)
    f32 with +inf on invalid pairs, evals (n,) int32)."""
    return join_call(_join_dists_q8_kernel, "knn_join_dists_q8", xq,
                     [x2g, xscale], ids, cn=cn, tb=tb, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("cn", "tb", "interpret"))
def knn_join_dists_bf16_blocked(
    xg: jax.Array,     # (n, C, dp) bf16 gathered candidate rows
    x2g: jax.Array,    # (n, C) cached bf16 squared norms (0 invalid)
    ids: jax.Array,    # (n, C) candidate node ids, -1 = invalid slot
    *,
    cn: int,
    tb: int | None = None,   # rows per block (None: from VMEM bytes)
    interpret: bool = False,
):
    """Blocked bf16 local-join pair distances. Same contract as the int8
    form minus the scales."""
    return join_call(_join_dists_bf16_kernel, "knn_join_dists_bf16", xg,
                     [x2g], ids, cn=cn, tb=tb, interpret=interpret)
