"""Selection step — paper §3.1, heap-free "turbosampling", TPU form.

Per NN-Descent iteration, every node u needs a bounded sample of its
neighborhood N(u) = adj(u) ∪ adj⁻¹(u) (forward and reverse edges of the
current graph), split into "new" and "old" pools (incremental search).

The paper's progression, reproduced here:
  naive (3 passes: reverse, union, sample)   -> selection_naive()
  PyNNDescent fused one-pass w/ heaps        -> selection_heap()
  turbosampling: heap-free, per-edge accept  -> selection_turbo()
     with prob rho*k/|N(u)|, expectation-equal to random-weight heaps

The TPU realization of turbosampling is fully dense: reverse degrees come
from one segment_sum over the edge list; each directed (receiver,
candidate) incidence is accepted by an independent Bernoulli with that
probability; accepted incidences are compacted into fixed (n, C) buffers by
a single (receiver, random) sort — no heap, no dynamic shapes, and the sort
replaces the paper's cache-resident incremental inserts (assumption change
#5 in DESIGN.md).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import sorting
from repro.core.heap import NeighborLists
from repro.core.layout import flatten_rows


class Candidates(NamedTuple):
    new_idx: jax.Array   # (n, c_new) i32, -1 = empty
    old_idx: jax.Array   # (n, c_old) i32, -1 = empty
    sampled_fwd: jax.Array  # (n, k) bool: forward new slots sampled this round


def _incidences(nl: NeighborLists):
    """All directed (receiver, candidate, is_new, is_forward_slot) triples.

    Forward: u receives its own adjacency; reverse: v = adj(u) receives u.
    Shapes: (2*n*k,) flattened, slot index retained for flag clearing.
    """
    n, k = nl.idx.shape
    valid = nl.idx >= 0
    fwd_recv = jnp.arange(n * k, dtype=jnp.int32) // k
    fwd_cand = flatten_rows(jnp.where(valid, nl.idx, 0))
    rev_recv = fwd_cand
    rev_cand = fwd_recv
    is_new = flatten_rows(nl.new)
    valid = flatten_rows(valid)
    recv = jnp.concatenate([fwd_recv, rev_recv])
    cand = jnp.concatenate([fwd_cand, rev_cand])  # candidate for receiver
    new = jnp.concatenate([is_new, is_new])
    val = jnp.concatenate([valid, valid])
    is_fwd = jnp.concatenate(
        [jnp.ones_like(valid), jnp.zeros_like(valid)]
    )
    slot = jnp.tile(jnp.arange(n * k, dtype=jnp.int32), 2)
    return recv, cand, new, val, is_fwd, slot


def _compact(
    recv: jax.Array,
    cand: jax.Array,
    accept: jax.Array,
    rnd: jax.Array,
    n: int,
    c: int,
) -> jax.Array:
    """Compact accepted (receiver, candidate) incidences into an (n, c)
    buffer: sort by (receiver, random), keep the first c per receiver.
    This is uniform reservoir sampling of the accepted set. The random
    order is quantized into the low bits of one packed int32 sort key
    (core/sorting.py: a two-key lexsort compiles for minutes on TPU);
    equal draws tie in no set order, which is still a uniform draw."""
    key_recv = jnp.where(accept, recv, n)  # rejected sort to the end
    rank = (rnd * 2.0**31).astype(jnp.int32)
    order = sorting.packed_argsort(key_recv, rank, n)
    recv_s = key_recv[order]
    cand_s = cand[order]
    # position within the receiver's group
    first = jnp.searchsorted(recv_s, jnp.arange(n + 1), side="left")
    pos = jnp.arange(recv_s.shape[0]) - first[jnp.clip(recv_s, 0, n)]
    # writes with recv_s == n (rejected) or pos >= c (overflow) fall out of
    # bounds and are dropped — exactly the semantics we want.
    out = jnp.full((n, c), -1, dtype=jnp.int32)
    out = out.at[recv_s, pos].set(cand_s, mode="drop")
    return out


def selection_turbo(
    key: jax.Array,
    nl: NeighborLists,
    rho_k: int,
) -> Candidates:
    """Heap-free turbosampling (paper C2). rho_k = max candidates per pool."""
    n, k = nl.idx.shape
    recv, cand, is_new, valid, is_fwd, slot = _incidences(nl)

    # |N(u)| = forward degree (k) + reverse degree, per pool (new/old)
    def pool_degree(mask):
        return jax.ops.segment_sum(
            mask.astype(jnp.int32), recv, num_segments=n
        )

    deg_new = pool_degree(valid & is_new)
    deg_old = pool_degree(valid & ~is_new)

    k_acc, k_new, k_old = jax.random.split(key, 3)
    p_new = jnp.minimum(1.0, rho_k / jnp.maximum(deg_new, 1))[recv]
    p_old = jnp.minimum(1.0, rho_k / jnp.maximum(deg_old, 1))[recv]
    u = jax.random.uniform(k_acc, recv.shape)
    acc_new = valid & is_new & (u < p_new)
    acc_old = valid & ~is_new & (u < p_old)

    rnd_new = jax.random.uniform(k_new, recv.shape)
    rnd_old = jax.random.uniform(k_old, recv.shape)
    new_buf = _compact(recv, cand, acc_new, rnd_new, n, rho_k)
    old_buf = _compact(recv, cand, acc_old, rnd_old, n, rho_k)

    # forward new slots that were accepted are "joined" -> clear their flag
    nk = n * k
    sampled_fwd = jnp.zeros((nk,), dtype=bool)
    sampled_fwd = sampled_fwd.at[jnp.where(acc_new & is_fwd, slot, 0)].max(
        acc_new & is_fwd
    )
    return Candidates(new_buf, old_buf, sampled_fwd.reshape(n, k))


def selection_heap(
    key: jax.Array,
    nl: NeighborLists,
    rho_k: int,
) -> Candidates:
    """PyNNDescent-style fused selection (paper C1): draw one uniform weight
    per incidence, keep the rho_k smallest per receiver. Same output
    distribution family as turbosampling but samples exactly rho_k when
    available. Realized with the same sort machinery (the 'heap' is the
    per-receiver top-rho_k of the random weights)."""
    n, k = nl.idx.shape
    recv, cand, is_new, valid, is_fwd, slot = _incidences(nl)
    k_w, _ = jax.random.split(key)
    w = jax.random.uniform(k_w, recv.shape)
    new_buf = _compact(recv, cand, valid & is_new, w, n, rho_k)
    old_buf = _compact(recv, cand, valid & ~is_new, w, n, rho_k)
    # mark all forward new slots whose weight put them in the sample —
    # conservative approximation: mark accepted incidences like turbo
    sampled = jnp.zeros((n * k,), dtype=bool)
    # a forward slot is sampled if its incidence survived compaction; we
    # approximate with weight-rank acceptance probability rho_k/deg:
    deg_new = jax.ops.segment_sum(
        (valid & is_new).astype(jnp.int32), recv, num_segments=n
    )
    p = jnp.minimum(1.0, rho_k / jnp.maximum(deg_new, 1))[recv]
    acc = valid & is_new & (w < p)
    sampled = sampled.at[jnp.where(acc & is_fwd, slot, 0)].max(acc & is_fwd)
    return Candidates(new_buf, old_buf, sampled.reshape(n, k))


def selection_naive(
    key: jax.Array,
    nl: NeighborLists,
    rho_k: int,
) -> Candidates:
    """The paper's baseline: three explicit passes (reverse, union, sample)
    with materialized intermediates. Functionally identical output family;
    kept as the benchmark baseline for §4.1. The reverse adjacency is
    materialized into a bounded (n, r_max) buffer (r_max = 2k) — the
    'dynamically growing data structure' cost the fused versions avoid."""
    n, k = nl.idx.shape
    r_max = 2 * k
    recv, cand, is_new, valid, is_fwd, slot = _incidences(nl)
    # pass 1: materialize reverse adjacency (bounded stand-in for the
    # paper's dynamically-growing reverse lists)
    half = n * k
    rev_recv, rev_cand = recv[half:], cand[half:]
    rev_valid = valid[half:]
    k1, k2, k3 = jax.random.split(key, 3)
    rev_rnd = jax.random.uniform(k1, rev_recv.shape)
    rev_buf = _compact(rev_recv, rev_cand, rev_valid, rev_rnd, n, r_max)
    rev_new_buf = _compact(
        rev_recv, rev_cand, rev_valid & is_new[half:], rev_rnd, n, r_max
    )
    # pass 2: union with forward adjacency (flags carried per pool)
    union_idx = jnp.concatenate([nl.idx, rev_buf], axis=1)        # (n, 3k)
    in_rev_new = (rev_buf[:, :, None] == rev_new_buf[:, None, :]).any(-1)
    union_new = jnp.concatenate([nl.new, in_rev_new], axis=1)
    valid_u = union_idx >= 0

    # pass 3: sample rho_k per pool
    def sample(mask, kk):
        ww = jnp.where(mask, jax.random.uniform(kk, union_idx.shape), jnp.inf)
        order = jnp.argsort(ww, axis=1)[:, :rho_k]
        got = jnp.take_along_axis(union_idx, order, axis=1)
        ok = jnp.take_along_axis(ww, order, axis=1) < jnp.inf
        return jnp.where(ok, got, -1)

    new_buf = sample(valid_u & union_new, k2)
    old_buf = sample(valid_u & ~union_new, k3)
    # flag clearing: same policy as turbo (forward slots present in sample)
    sampled = (nl.idx[:, :, None] == new_buf[:, None, :]).any(-1) & nl.new
    return Candidates(new_buf, old_buf, sampled)
