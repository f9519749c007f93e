"""Bounded, sorted neighbor lists — the vectorized form of NN-Descent's
per-node "heap" (paper §3.1 removes real heaps; so do we, for the same
reason on different hardware: heaps are pointer-chasing and cache-hostile
on CPU, and dynamically-shaped and scatter-hostile on TPU).

Representation: per node, k slots of (distance ascending, id), with
(inf, -1) for empty slots, plus a "new" flag per slot for NN-Descent's
incremental search.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.layout import chunked_rows
from repro.kernels import ops


class NeighborLists(NamedTuple):
    dist: jax.Array   # (n, k) f32, ascending, inf = empty
    idx: jax.Array    # (n, k) i32, -1 = empty
    new: jax.Array    # (n, k) bool — not yet used in a join (incremental search)


def init_random(key: jax.Array, n: int, k: int) -> NeighborLists:
    """Uniform random initialization (paper §2), distances unevaluated (inf
    would break the merge ordering, so we store +big and mark all new;
    the first iteration's joins immediately replace them)."""
    idx = jax.random.randint(key, (n, k), 0, n, dtype=jnp.int32)
    # avoid self-loops: bump collisions by 1 (mod n)
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    idx = jnp.where(idx == rows, (idx + 1) % n, idx)
    dist = jnp.full((n, k), jnp.float32(3.0e38))
    new = jnp.ones((n, k), dtype=bool)
    return NeighborLists(dist, idx, new)


@functools.partial(jax.jit, static_argnames=("k", "backend"))
def init_random_with_dists(
    key: jax.Array, x: jax.Array, k: int, *, backend: str = "auto"
) -> NeighborLists:
    """Random init with true distances evaluated (norm-expansion form).
    One jitted program, rows 1024 at a time: the whole (n, k, d) gather
    is n*k*d*4 bytes, and whole-array gathers and sorts over (n, k)
    compile for minutes on TPU (150.8 s at 131,072 rows for a v5e;
    chunked, 2-3 s)."""
    n = x.shape[0]
    nl = init_random(key, n, k)
    xf = x.astype(jnp.float32)
    x2 = jnp.sum(xf * xf, axis=1)

    def rows(args):
        xr, x2r, ir = args
        ab = jnp.einsum("nd,nkd->nk", xr, xf[ir],
                        precision=jax.lax.Precision.HIGHEST)
        d = jnp.maximum(x2r[:, None] + x2[ir] - 2.0 * ab, 0.0)
        return jax.lax.sort((d, ir), dimension=1, num_keys=1)

    d, idx = chunked_rows(rows, (xf, x2, nl.idx),
                          min(1024, ((n + 7) // 8) * 8))
    return NeighborLists(d, idx, jnp.ones((n, k), dtype=bool))


def merge(
    nl: NeighborLists,
    cand_dist: jax.Array,
    cand_idx: jax.Array,
    cand_new: bool = True,
    *,
    backend: str = "auto",
) -> tuple[NeighborLists, jax.Array]:
    """Merge candidate (dist, id) pairs into the lists. Returns
    (updated lists, per-node accepted count). Accepted slots get the
    ``new`` flag; surviving slots keep theirs."""
    n, k = nl.dist.shape
    all_dist = jnp.concatenate([nl.dist, cand_dist], axis=1)
    all_idx = jnp.concatenate([nl.idx, cand_idx], axis=1)
    all_flag = jnp.concatenate(
        [nl.new, jnp.full(cand_idx.shape, cand_new)], axis=1
    )
    # invalidate duplicates (candidate already present / repeated candidate)
    c = cand_idx.shape[1]
    dup_graph = (cand_idx[:, :, None] == nl.idx[:, None, :]).any(-1)
    eq = cand_idx[:, :, None] == cand_idx[:, None, :]
    earlier = jnp.tril(jnp.ones((c, c), dtype=bool), k=-1)[None]
    dup = dup_graph | (eq & earlier).any(-1) | (cand_idx < 0)
    all_dist = all_dist.at[:, k:].set(jnp.where(dup, jnp.inf, cand_dist))

    # one stable sort carrying its payloads (an argsort plus gathers of
    # the (n, k + c) rows compiles for much longer on TPU)
    col = jax.lax.broadcasted_iota(jnp.int32, all_idx.shape, 1)
    new_dist, col, new_idx, new_flag = (
        a[:, :k] for a in jax.lax.sort(
            (all_dist, col, all_idx, all_flag), dimension=1, num_keys=1))
    accepted = (col >= k) & jnp.isfinite(new_dist)
    updated = jnp.sum(accepted, axis=1).astype(jnp.int32)
    return NeighborLists(new_dist, new_idx, new_flag), updated


def merge_kernel(
    nl: NeighborLists,
    cand_dist: jax.Array,
    cand_idx: jax.Array,
    *,
    backend: str = "auto",
) -> tuple[NeighborLists, jax.Array]:
    """Kernel-backed merge (flags recomputed as 'accepted == new')."""
    new_dist, new_idx, updated = ops.knn_merge(
        nl.dist, nl.idx, cand_dist, cand_idx, backend=backend
    )
    # a slot is new iff it was not already present in the old list
    was_old = (new_idx[:, :, None] == nl.idx[:, None, :]).any(-1)
    keep_flag = jnp.where(
        was_old,
        # carry the old flag for surviving slots
        _lookup_flags(nl, new_idx),
        True,
    )
    return NeighborLists(new_dist, new_idx, keep_flag & (new_idx >= 0)), updated


def _lookup_flags(nl: NeighborLists, ids: jax.Array) -> jax.Array:
    hit = ids[:, :, None] == nl.idx[:, None, :]
    return (hit & nl.new[:, None, :]).any(-1)


def merge_block(
    nl: NeighborLists,
    start: jax.Array,
    cand_dist: jax.Array,
    cand_idx: jax.Array,
    *,
    backend: str = "auto",
) -> tuple[NeighborLists, jax.Array]:
    """Chunked merge entry point: merge (R, c) candidates into the
    CONTIGUOUS row block [start, start+R) — the fused local join's
    receiver chunks (core/nn_descent.py local_join_fused). Receivers are
    rows, so no id dedup/scatter is needed: one dynamic slice in, the
    blocked merge kernel, one dynamic slice out. ``start`` must satisfy
    start + R <= n (the fused driver pads the lists to a chunk multiple).
    Returns (lists, (R,) accepted counts)."""
    r, _ = cand_dist.shape
    k = nl.dist.shape[1]
    sub_d = jax.lax.dynamic_slice(nl.dist, (start, 0), (r, k))
    sub_i = jax.lax.dynamic_slice(nl.idx, (start, 0), (r, k))
    sub_n = jax.lax.dynamic_slice(nl.new, (start, 0), (r, k))
    md, mi, upd = ops.knn_merge(
        sub_d, sub_i, cand_dist, cand_idx, backend=backend
    )
    old_sub = NeighborLists(sub_d, sub_i, sub_n)
    was_old = (mi[:, :, None] == sub_i[:, None, :]).any(-1)
    flag = jnp.where(
        was_old, _lookup_flags(old_sub, mi), True
    ) & (mi >= 0)
    return NeighborLists(
        jax.lax.dynamic_update_slice(nl.dist, md, (start, 0)),
        jax.lax.dynamic_update_slice(nl.idx, mi, (start, 0)),
        jax.lax.dynamic_update_slice(nl.new, flag, (start, 0)),
    ), upd


def merge_rows(
    nl: NeighborLists,
    rows: jax.Array,
    cand_dist: jax.Array,
    cand_idx: jax.Array,
    *,
    backend: str = "auto",
) -> tuple[NeighborLists, jax.Array]:
    """Frontier merge: merge (f, c) candidates into rows ``rows`` only
    (-1 = padding; ids must be unique). All flag bookkeeping happens on
    the gathered (f, k) sub-lists, so the cost is O(f), not O(n).
    Returns (lists, per-frontier-row accepted count)."""
    n, _ = nl.dist.shape
    ok = rows >= 0
    safe = jnp.where(ok, rows, 0)
    old_sub = NeighborLists(nl.dist[safe], nl.idx[safe], nl.new[safe])
    new_dist, new_idx, upd = ops.knn_merge_rows(
        nl.dist, nl.idx, rows, cand_dist, cand_idx, backend=backend
    )
    sub_i = new_idx[safe]
    was_old = (sub_i[:, :, None] == old_sub.idx[:, None, :]).any(-1)
    flag_sub = jnp.where(
        was_old, _lookup_flags(old_sub, sub_i), True
    ) & (sub_i >= 0)
    tgt = jnp.where(ok, rows, n)
    new_flag = nl.new.at[tgt].set(flag_sub, mode="drop")
    return NeighborLists(new_dist, new_idx, new_flag), upd


def purge_rows(
    nl: NeighborLists, rows: jax.Array, alive: jax.Array, *,
    backend: str = "auto",
) -> tuple[NeighborLists, jax.Array]:
    """Frontier purge: drop dead-target edges from rows ``rows`` only, and
    empty the lists of rows that are themselves dead (the online delete
    path puts both kinds on the compaction frontier). Survivors stay
    sorted/packed; freed slots become (inf, -1, False). Returns
    (lists, per-frontier-row removed count)."""
    n = alive.shape[0]
    ok = rows >= 0
    safe = jnp.where(ok, rows, 0)
    sub_i = nl.idx[safe]
    sub_valid = sub_i >= 0
    drop = sub_valid & ~alive[jnp.clip(sub_i, 0, n - 1)]
    drop |= sub_valid & ~alive[safe][:, None]       # dead row: clear it all
    new_dist, new_idx, removed = ops.knn_compact_rows(
        nl.dist, nl.idx, rows, drop, backend=backend
    )
    sub_new = new_idx[safe]
    flag_sub = _lookup_flags(
        NeighborLists(nl.dist[safe], sub_i, nl.new[safe]), sub_new
    ) & (sub_new >= 0)
    tgt = jnp.where(ok, rows, n)
    new_flag = nl.new.at[tgt].set(flag_sub, mode="drop")
    return NeighborLists(new_dist, new_idx, new_flag), removed


def purge(
    nl: NeighborLists, alive: jax.Array, *, backend: str = "auto"
) -> tuple[NeighborLists, jax.Array]:
    """Remove edges pointing at dead nodes (``alive[idx] == False``).

    Survivors stay sorted and packed to the front; freed slots become
    (inf, -1, False). Returns (lists, per-node removed count) — the online
    delete path (core/online.py) refills rows where removed > 0."""
    n = alive.shape[0]
    valid = nl.idx >= 0
    drop = valid & ~alive[jnp.clip(nl.idx, 0, n - 1)]
    new_dist, new_idx, removed = ops.knn_compact(
        nl.dist, nl.idx, drop, backend=backend
    )
    flag = _lookup_flags(nl, new_idx) & (new_idx >= 0)
    return NeighborLists(new_dist, new_idx, flag), removed


def mark_sampled_old(nl: NeighborLists, sampled_mask: jax.Array) -> NeighborLists:
    """Clear the 'new' flag of forward slots that were sampled this round
    (NN-Descent incremental search: a pair is joined at most once)."""
    return nl._replace(new=nl.new & ~sampled_mask)
