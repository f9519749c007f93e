"""NN-Descent (Dong et al., WWW'11) — fixed-shape JAX implementation with
the paper's optimizations (turbosampling selection, blocked distance
evaluation, greedy memory reordering).

One iteration (jitted, static shapes):
  1. selection (core/selection.py): bounded new/old candidate buffers
  2. local joins: all new x new and new x old candidate pairs get their
     squared-l2 distance via the norm-expansion (MXU) form with cached
     squared norms — the batched counterpart of kernels/l2_blocked.py
  3. update routing: each evaluated pair is a candidate for BOTH endpoints.
     The FUSED path (``DescentConfig.backend`` auto/pallas/interpret,
     ``local_join_fused``) keeps routing receiver-local: the per-row pair
     tensor is computed by the blocked ``knn_join_dists`` kernel, one
     stable argsort of the n*C candidate incidences tells every receiver
     which (row, slot) positions list it (``invert_candidates``), each
     receiver gathers its incoming distance rows and the
     ``knn_join_select`` kernel reduces them to the best merge_k under the
     k-th-distance prefilter; receivers are then contiguous rows, so the
     merge is a sort-free chunked block merge (heap.merge_block). The REF
     path (backend="ref") keeps the seed implementation — flatten all
     pairs into an O(n*C^2) (receiver, candidate, dist) list, global
     (receiver, dist) lexsort (``compact_pairs``), one dense merge — and
     serves as the parity oracle for the fused path.
  4. convergence: stop when accepted updates < delta * n * k

The driver runs iterations from Python so the greedy reorder (paper §3.2)
can permute the point array between iterations (the permutation changes
array contents, not shapes, so the jitted iteration is reused).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import heap, quantize, selection, sorting
from repro.core import metric as metric_mod
from repro.core.heap import NeighborLists
from repro.core.layout import chunked_rows, flatten_rows, pad_features
from repro.core.quantize import QuantizedStore
from repro.core.reorder import apply_permutation, greedy_reorder
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class DescentConfig:
    k: int = 20
    rho: float = 0.5           # sample rate: rho*k candidates per pool
    max_iters: int = 12
    delta: float = 0.001       # stop when updates < delta*n*k (paper §2)
    merge_size: int = 0        # merge buffer per node (0 = 3*k)
    selection: str = "turbo"   # turbo | heap | naive  (paper's 3 tiers)
    reorder: bool = True       # paper §3.2 greedy reordering
    reorder_after: int = 1     # run reorder after this iteration (1 = paper)
    polish: int = 2            # terminal full local-join rounds: after the
                               # sampled iterations stop, join every node
                               # against ALL k*k neighbors-of-neighbors
                               # (unsampled). The sampled descent converges
                               # to a local optimum missing a thin tail of
                               # edges; the exhaustive polish recovers most
                               # of it for n*k^2 evals per round.
    backend: str = "auto"      # kernel dispatch (auto|pallas|interpret use
                               # the fused local join; "ref" keeps the
                               # global-lexsort compact_pairs oracle path)
    block_k: int = 512         # feature-axis block for norm expansion
    fetch: str = "a2a"         # distributed feature fetch: a2a | ring
    join_chunk: int = 2048     # fused join: receiver rows per chunk
    join_src: int = 0          # fused join: per-receiver source-incidence
                               # buffer (0 = 2*C); overflow beyond it is
                               # dropped (bounded-buffer sampling noise,
                               # like every other buffer in NN-Descent)
    metric: str = "l2"         # l2 | cosine | mips — realized by the
                               # input-side reductions of core/metric.py
                               # (cosine: row-normalize; mips: augmented
                               # coordinate, d -> d+1) applied ONCE at
                               # build entry; every join/select/merge
                               # below stays pure squared l2. Graph
                               # distances come back in the TRANSFORMED
                               # space — monotone in the native metric.
                               # All backends, "ref" included.
    precision: str = "f32"     # f32 | bf16 | int8 — candidate-SCORING
                               # dtype of the sampled local joins
                               # (kernels/l2_quant.py over a quantized
                               # corpus mirror). Quantized builds re-rank
                               # every surviving list fp32 after the
                               # sampled iterations (rerank_lists) and run
                               # the terminal polish rounds fp32, so the
                               # returned graph distances stay exact.
                               # backend="ref" (the lexsort parity oracle)
                               # is always fp32 and ignores this knob.

    @property
    def rho_k(self) -> int:
        return max(1, int(round(self.rho * self.k)))

    @property
    def merge_k(self) -> int:
        return self.merge_size or 3 * self.k


@dataclasses.dataclass
class DescentStats:
    iters: int = 0
    dist_evals: int = 0
    updates: tuple = ()
    polish_updates: tuple = ()
    reordered: bool = False
    # online-update frontier accounting (core/online.py): how many store
    # rows the update actually touched (actual / after chunk padding) —
    # the observable that update cost is O(frontier), not O(n)
    frontier_rows: int = 0
    padded_rows: int = 0

    def flops(self, d: int) -> int:
        """Paper §2 cost model: d subs + d mults + (d-1) adds per eval."""
        return self.dist_evals * (3 * d - 1)


_SELECT: dict[str, Callable] = {
    "turbo": selection.selection_turbo,
    "heap": selection.selection_heap,
    "naive": selection.selection_naive,
}


def pair_block(xg: jax.Array, x2g: jax.Array, yg: jax.Array, y2g: jax.Array):
    """Batched norm-expansion distances: (n,a,d)x(n,b,d) -> (n,a,b)."""
    ab = jnp.einsum(
        "nad,nbd->nab", xg, yg, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    out = x2g[:, :, None] + y2g[:, None, :] - 2.0 * ab
    return jnp.maximum(out, 0.0)


def compact_pairs(recv, cand, dist, n: int, c: int):
    """Group flattened (receiver, candidate, dist) updates into per-node
    (n, c) buffers keeping the c best (smallest distance) per receiver."""
    valid = recv >= 0
    key_recv = jnp.where(valid, recv, n)
    order = sorting.lex_argsort(sorting.sortable_bits(dist), key_recv)
    recv_s = key_recv[order]
    cand_s = cand[order]
    dist_s = dist[order]
    first = jnp.searchsorted(recv_s, jnp.arange(n + 1), side="left")
    pos = jnp.arange(recv_s.shape[0]) - first[jnp.clip(recv_s, 0, n)]
    out_i = jnp.full((n, c), -1, dtype=jnp.int32)
    out_d = jnp.full((n, c), jnp.inf, dtype=jnp.float32)
    out_i = out_i.at[recv_s, pos].set(cand_s, mode="drop")
    out_d = out_d.at[recv_s, pos].set(dist_s, mode="drop")
    return out_d, out_i


def invert_candidates(
    cands: jax.Array, n_univ: int, src_cap: int,
    prio: jax.Array | None = None,
):
    """Invert (row -> candidate) incidences: for every candidate id in
    [0, n_univ), the (row, slot) positions that list it, compacted into
    (n_univ, src_cap) padded buffers (-1 tail). Overflow beyond src_cap:
    with ``prio`` (same shape as ``cands``, a non-negative distance) the
    LOWEST priority incidences are kept per candidate — the old smallest-
    (row, slot) policy was a systematic bias against late rows on
    hub-heavy buffers; without ``prio`` the old deterministic id order
    is preserved (pure adjacency inversions have no distance to rank by).

    One single-key sort of the n*C incidence ids — the only sort left in
    the fused build hot path, ~pairs/C times smaller than the retired
    global pair sort. The priority rides in the low bits of the packed
    key (core/sorting.py), so it ranks to about 1/128 relative; near-ties
    fall in no set order."""
    nr, c = cands.shape
    flat = flatten_rows(cands)
    key = jnp.where(flat >= 0, flat, n_univ)
    if prio is None:
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
    else:
        # candidate-major, priority-minor
        order = sorting.packed_argsort(
            key, sorting.sortable_bits(flatten_rows(prio)), n_univ)
    rs = key[order]
    first = jnp.searchsorted(rs, jnp.arange(n_univ + 1))
    pos = jnp.arange(nr * c) - first[jnp.clip(rs, 0, n_univ)]
    rows_of = jnp.full((n_univ, src_cap), -1, jnp.int32)
    slot_of = jnp.full((n_univ, src_cap), -1, jnp.int32)
    rows_of = rows_of.at[rs, pos].set(order // c, mode="drop")
    slot_of = slot_of.at[rs, pos].set(order % c, mode="drop")
    return rows_of, slot_of


def local_join_fused(
    x: jax.Array,          # (n, dp) feature-padded points
    x2: jax.Array,         # (n,) cached squared norms
    nl: NeighborLists,
    cn: jax.Array,         # (n, Cn) new candidates
    co: jax.Array,         # (n, Co) old candidates
    cfg: DescentConfig,
    qs: QuantizedStore | None = None,   # quantized corpus mirror
):
    """Fused local join + update routing (no flattened pair list, no
    global lexsort): blocked pair-distance kernel -> incidence inversion
    -> per-receiver gather + prefiltered top-merge_k select kernel ->
    chunked block merge. Returns (nl, accepted, evals).

    With ``qs`` given and ``cfg.precision`` quantized, the pair tensor is
    scored by the int8/bf16 kernel over the quantized rows (2-4x fewer
    gathered bytes per candidate) — the build face of the two-stage path;
    the driver re-ranks the final lists fp32 (``rerank_lists``)."""
    n, k = nl.idx.shape
    cands = jnp.concatenate([cn, co], axis=1)        # (n, C)
    c_all = cands.shape[1]
    valid = cands >= 0
    safe = jnp.where(valid, cands, 0)
    ids = jnp.where(valid, cands, -1)
    def join_rows(args):
        sf, vl, idr = args
        if cfg.precision != "f32" and qs is not None:
            x2g = jnp.where(vl, qs.x2[sf], 0.0)
            if cfg.precision == "int8":
                return ops.knn_join_dists_q8(
                    qs.data[sf], qs.scale[sf], x2g, idr, cn=cn.shape[1],
                    backend=cfg.backend,
                )
            return ops.knn_join_dists_bf16(
                qs.data[sf], x2g, idr, cn=cn.shape[1], backend=cfg.backend,
            )
        x2g = jnp.where(vl, x2[sf], 0.0)
        return ops.knn_join_dists(
            x[sf], x2g, idr, cn=cn.shape[1], backend=cfg.backend
        )

    # the (rows, C, dp) feature gather feeding the kernel goes
    # join_chunk rows at a time: whole, it is n*C*dp*4 bytes (8.6 GB at
    # 60,000 x 784 with C=40 — past a 16 GB chip with the rest of the
    # iteration)
    r = min(cfg.join_chunk, ((n + 7) // 8) * 8)
    dists, ev = chunked_rows(join_rows, (safe, valid, ids), r)

    kth = nl.dist[:, -1]
    s_cap = cfg.join_src or 2 * c_all
    # overflow priority: each (row, slot) incidence contributes the row's
    # pair distances to the candidate — rank it by the best distance it
    # can offer, so buffer overflow drops the least useful incidences
    # instead of the highest (row, slot)
    inc_prio = dists.min(axis=2)                     # (n, C)
    rows_of, slot_of = invert_candidates(cands, n, s_cap, prio=inc_prio)

    # receiver chunks: pad everything to a chunk multiple so every merge
    # is a full in-bounds block (padding rows have no incidences -> no-op)
    npad = ((n + r - 1) // r) * r
    pad = npad - n
    rows_of = jnp.pad(rows_of, ((0, pad), (0, 0)), constant_values=-1)
    slot_of = jnp.pad(slot_of, ((0, pad), (0, 0)), constant_values=-1)
    kth_p = jnp.pad(kth, (0, pad))
    nl_p = NeighborLists(
        jnp.pad(nl.dist, ((0, pad), (0, 0)), constant_values=jnp.inf),
        jnp.pad(nl.idx, ((0, pad), (0, 0)), constant_values=-1),
        jnp.pad(nl.new, ((0, pad), (0, 0))),
    )
    d_flat = dists.reshape(n * c_all, c_all)

    def body(j, carry):
        nl_j, upd = carry
        sl = jax.lax.dynamic_slice(rows_of, (j * r, 0), (r, s_cap))
        so = jax.lax.dynamic_slice(slot_of, (j * r, 0), (r, s_cap))
        ok = sl >= 0
        lin = jnp.where(ok, sl * c_all + so, 0)
        gd = jnp.where(ok[:, :, None], d_flat[lin], jnp.inf)
        gi = jnp.where(ok[:, :, None], ids[jnp.where(ok, sl, 0)], -1)
        kth_j = jax.lax.dynamic_slice(kth_p, (j * r,), (r,))
        cd, ci = ops.knn_join_select(
            gd.reshape(r, s_cap * c_all),
            gi.reshape(r, s_cap * c_all),
            kth_j, c=cfg.merge_k, backend=cfg.backend,
        )
        nl_j, u = heap.merge_block(nl_j, j * r, cd, ci,
                                   backend=cfg.backend)
        return nl_j, upd + jnp.sum(u)

    nl_p, upd = jax.lax.fori_loop(
        0, npad // r, body, (nl_p, jnp.zeros((), jnp.int32))
    )
    nl = NeighborLists(nl_p.dist[:n], nl_p.idx[:n], nl_p.new[:n])
    return nl, upd, jnp.sum(ev)


@functools.partial(jax.jit, static_argnames=("cfg",))
def nn_descent_iteration(
    key: jax.Array,
    x: jax.Array,          # (n, d) — feature-padded
    x2: jax.Array,         # (n,) cached squared norms (beyond-paper reuse)
    nl: NeighborLists,
    cfg: DescentConfig,
    qs: QuantizedStore | None = None,   # quantized mirror (precision != f32)
):
    n, k = nl.idx.shape
    cands = _SELECT[cfg.selection](key, nl, cfg.rho_k)
    nl = heap.mark_sampled_old(nl, cands.sampled_fwd)

    cn = cands.new_idx          # (n, Cn)
    co = cands.old_idx          # (n, Co)
    if cfg.backend != "ref":
        return local_join_fused(x, x2, nl, cn, co, cfg, qs)
    vn = cn >= 0
    vo = co >= 0
    xg_n = x[jnp.where(vn, cn, 0)]
    xg_o = x[jnp.where(vo, co, 0)]
    x2_n = jnp.where(vn, x2[jnp.where(vn, cn, 0)], 0.0)
    x2_o = jnp.where(vo, x2[jnp.where(vo, co, 0)], 0.0)

    d_nn = pair_block(xg_n, x2_n, xg_n, x2_n)   # (n, Cn, Cn)
    d_no = pair_block(xg_n, x2_n, xg_o, x2_o)   # (n, Cn, Co)

    cn_b = cn.shape[1]
    co_b = co.shape[1]
    iu = jnp.triu_indices(cn_b, k=1)
    # --- new x new (unordered pairs i<j, both directions)
    a_nn = cn[:, iu[0]]
    b_nn = cn[:, iu[1]]
    dd_nn = d_nn[:, iu[0], iu[1]]
    ok_nn = vn[:, iu[0]] & vn[:, iu[1]] & (a_nn != b_nn)
    # --- new x old (all pairs, both directions)
    a_no = jnp.broadcast_to(cn[:, :, None], (n, cn_b, co_b)).reshape(n, -1)
    b_no = jnp.broadcast_to(co[:, None, :], (n, cn_b, co_b)).reshape(n, -1)
    dd_no = d_no.reshape(n, -1)
    ok_no = (
        jnp.broadcast_to(vn[:, :, None], (n, cn_b, co_b)).reshape(n, -1)
        & jnp.broadcast_to(vo[:, None, :], (n, cn_b, co_b)).reshape(n, -1)
        & (a_no != b_no)
    )

    a = flatten_rows(jnp.concatenate([a_nn, b_nn, a_no, b_no], axis=1))
    b = flatten_rows(jnp.concatenate([b_nn, a_nn, b_no, a_no], axis=1))
    dd = flatten_rows(jnp.concatenate([dd_nn, dd_nn, dd_no, dd_no], axis=1))
    ok = flatten_rows(jnp.concatenate([ok_nn, ok_nn, ok_no, ok_no], axis=1))

    # receiver-side prefilter: only pairs beating the receiver's current
    # k-th distance can change the graph (saves the sort+merge cost)
    kth = nl.dist[:, -1]
    ok &= dd < kth[jnp.where(ok, a, 0)]
    recv = jnp.where(ok, a, -1)

    cand_d, cand_i = compact_pairs(recv, b, dd, n, cfg.merge_k)
    nl, upd = heap.merge(nl, cand_d, cand_i, cand_new=True)

    n_evals = jnp.sum(ok_nn) + jnp.sum(ok_no)   # unordered evaluations
    return nl, jnp.sum(upd), n_evals


@functools.partial(jax.jit, static_argnames=("backend",))
def polish_iteration(
    x: jax.Array,          # (n, d) — feature-padded
    x2: jax.Array,         # (n,) cached squared norms
    nl: NeighborLists,
    backend: str = "auto",
):
    """One exhaustive local-join round: every node joins against ALL k*k
    of its neighbors-of-neighbors (no sampling, forward direction). Run
    after the sampled iterations terminate — the stochastic descent
    converges to a local optimum that still misses a thin tail of edges
    reachable within two hops, and the unsampled join recovers them for a
    flat n*k^2 evaluations. Returns (nl, accepted, evals).

    With a non-"ref" backend the k*k candidate row is reduced by the
    fused ``knn_join_select`` kernel (k-th-distance prefilter + partial
    top-6k) before the merge, so the bounded-list merge runs at width 6k
    instead of k*k — the same fused-selection idea as the sampled
    iterations. 6k (not 3k) because NoN rows are heavily duplicated in
    clustered data and the merge dedups: at 3k the duplicates crowd out
    enough distinct candidates to cost ~0.7% recall on the 512-pt
    regression; at 6k the fused polish matches the full-width oracle.
    backend="ref" keeps the direct full-width merge (oracle).
    """
    n, k = nl.idx.shape

    def rows(args):
        xr, x2r, rr, dr, ir, fr = args
        nb = nl.idx[jnp.clip(ir, 0, n - 1)].reshape(-1, k * k)
        src_ok = jnp.repeat(ir >= 0, k, axis=1)
        ok = src_ok & (nb >= 0) & (nb != rr[:, None])
        safe = jnp.clip(nb, 0, n - 1)
        ab = jnp.einsum("nd,ncd->nc", xr, x[safe],
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
        dd = jnp.where(ok, jnp.maximum(x2r[:, None] + x2[safe] - 2.0 * ab,
                                       0.0), jnp.inf)
        sub = NeighborLists(dr, ir, fr)
        if backend == "ref":
            sub, upd = heap.merge(sub, dd, jnp.where(ok, nb, -1))
        else:
            cd, ci = ops.knn_join_select(
                dd, jnp.where(ok, nb, -1), dr[:, -1],
                c=min(6 * k, k * k), backend=backend,
            )
            sub, upd = heap.merge(sub, cd, ci)
        return sub, upd, jnp.sum(ok, axis=1)

    # 512 rows at a time: the whole (n, k*k, dp) gather is 86 GB at
    # 60,000 x 784, k=20, and whole-array (n, k*k) gathers and sorts
    # compiled for 232.9 s at 131,072 x 128 for a v5e
    nl, upd, evals = chunked_rows(
        rows, (x, x2, jnp.arange(n, dtype=jnp.int32), *nl),
        min(512, ((n + 7) // 8) * 8))
    return nl, jnp.sum(upd), jnp.sum(evals)


@jax.jit
def rerank_lists(x: jax.Array, nl: NeighborLists):
    """Exact fp32 re-rank of every neighbor list: recompute d(row, idx)
    in difference form (``metric.exact_sq_l2``) and re-sort each row.
    Run after the quantized sampled iterations (quantized scoring
    decides which edges survive, this pass makes the stored distances
    and order exact before the polish rounds extend them) and once at
    the end of every build, so the returned graph never carries a
    norm-expansion value. Rows go 1024 at a time, bounding the
    (rows, k, dp) gather; the re-sort runs inside each chunk (over whole
    (n, k) arrays its gathers compiled for 164.2 s at 131,072 rows for a
    v5e). Cost: n*k distance evaluations."""
    n, k = nl.idx.shape

    def rows(args):
        xr, ir, fr = args
        dd = metric_mod.exact_sq_l2(xr, x[jnp.clip(ir, 0, n - 1)], ir)
        # stable, +inf (invalid) last
        return jax.lax.sort((dd, ir, fr), dimension=1, num_keys=1)

    return NeighborLists(*chunked_rows(
        rows, (x, nl.idx, nl.new), min(1024, ((n + 7) // 8) * 8)))


# the build loop's own array bookkeeping runs as jitted programs: op by
# op, each large gather/scatter was a TPU compile of its own (about 15 s
# apiece at 60,000 rows)

@jax.jit
def _take_rows(rows: jax.Array, arrays):
    """``a[rows]`` for every array of a pytree."""
    return jax.tree_util.tree_map(lambda a: a[rows], arrays)


@jax.jit
def _to_original_ids(nl: NeighborLists, perm: jax.Array):
    """Map lists back to original ids: row r describes original node
    perm[r]. Returns (dist, idx)."""
    n = perm.shape[0]
    inv = jnp.zeros_like(perm).at[perm].set(
        jnp.arange(n, dtype=perm.dtype))

    # 1024 rows at a time: whole-array gathers of the narrow (n, k)
    # lists compile for minutes on TPU
    def rows(a):
        ir = nl.idx[a[0]]
        return nl.dist[a[0]], jnp.where(
            ir >= 0, perm[jnp.clip(ir, 0, n - 1)], -1)

    return chunked_rows(rows, (inv,), min(1024, ((n + 7) // 8) * 8))


def build_knn_graph(
    x: jax.Array,
    k: int = 20,
    *,
    cfg: DescentConfig | None = None,
    key: jax.Array | None = None,
    callback: Callable | None = None,
):
    """Build an approximate K-NN graph of x (n, d).

    Returns (dist (n,k) f32 ascending, idx (n,k) i32 in ORIGINAL ids,
    stats). Deterministic given ``key``.

    ``cfg.metric`` selects l2 (default) / cosine / mips: the raw rows
    are reduced to an l2-equivalent form once, here (core/metric.py),
    and the whole descent below runs unchanged on the transformed rows.
    Returned distances are transformed-space squared l2 — neighbor ORDER
    is the native metric's; convert values with
    ``metric.similarity_from_dist`` if needed.
    """
    cfg = cfg or DescentConfig(k=k)
    if cfg.k != k:
        cfg = dataclasses.replace(cfg, k=k)
    key = jax.random.key(0) if key is None else key
    n = x.shape[0]
    x, _ = metric_mod.transform_corpus(x, cfg.metric)
    xp = pad_features(x.astype(jnp.float32))
    x2 = jnp.sum(xp * xp, axis=1)

    # two-stage quantized build: the sampled joins score on a quantized
    # corpus mirror (at the mirror's own width — the fp32 layout's zero
    # feature padding is dropped); rerank_lists + the polish rounds
    # restore exact fp32
    quant = cfg.precision != "f32" and cfg.backend != "ref"
    qs = (quantize.quantize_corpus(
        xp, cfg.precision,
        width=quantize.mirror_width(x.shape[1], xp.shape[1]))
        if quant else None)

    k_init, key = jax.random.split(key)
    nl = heap.init_random_with_dists(k_init, xp, cfg.k)
    stats = DescentStats(dist_evals=n * cfg.k)
    # running permutation: perm[new_pos] = original id
    perm = jnp.arange(n, dtype=jnp.int32)

    updates = []
    for it in range(cfg.max_iters):
        key, k_it = jax.random.split(key)
        nl, upd, ev = nn_descent_iteration(k_it, xp, x2, nl, cfg, qs)
        upd = int(upd)
        stats.dist_evals += int(ev)
        updates.append(upd)
        stats.iters = it + 1
        if callback is not None:
            callback(it, upd, nl)
        if cfg.reorder and it + 1 == cfg.reorder_after:
            sigma, sigma_inv = greedy_reorder(nl)
            xp, nl = apply_permutation(xp, nl, sigma, sigma_inv)
            # per-row quantization permutes exactly — no requantize
            x2, perm, qs = _take_rows(sigma_inv, (x2, perm, qs))
            stats.reordered = True
        if upd <= cfg.delta * n * cfg.k:
            break
    stats.updates = tuple(updates)

    # stage two of a quantized build: exact fp32 re-rank of the surviving
    # lists, so the polish rounds below merge against exact distances and
    # the returned graph never carries a quantized value
    if quant:
        nl = rerank_lists(xp, nl)
        stats.dist_evals += n * cfg.k

    # terminal polish (see DescentConfig.polish / polish_iteration)
    polish_updates = []
    for _p in range(cfg.polish):
        nl, upd_p, ev_p = polish_iteration(xp, x2, nl, cfg.backend)
        polish_updates.append(int(upd_p))
        stats.dist_evals += int(ev_p)
    stats.polish_updates = tuple(polish_updates)
    # returned distances are exact: difference form on the final lists
    nl = rerank_lists(xp, nl)

    dist, idx = _to_original_ids(nl, perm)
    return dist, idx, stats
