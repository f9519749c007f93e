"""Online K-NN graph updates: insert / delete without a full rebuild.

The paper's NN-Descent builds a *static* graph; a serving datastore must
absorb new points and retire stale ones while queries keep flowing. This
module adds that, built from the same primitives as the offline build:

  * ``knn_insert(store, new_points)`` — each new point is *seeded* by a
    greedy ``graph_search`` over the existing graph (the serving-side
    structure already answers "who is near q?"), then refined by a
    **localized NN-Descent**: a few friend-of-a-friend rounds that join
    each new point against the neighbors of its current neighbors
    (Dong et al.'s local-join restricted to the touched frontier), using
    the offline build's fused ``knn_join_select`` routing for the
    reverse-edge repair (``_route_reverse`` — invert incidences, gather,
    prefiltered top-c; no pair sort). Convergence is fast for the same
    reason NN-Descent's is: a
    neighbor of a neighbor is likely a neighbor, so a handful of seed
    candidates is enough to pull in the true neighborhood.

  * ``knn_delete(store, ids)`` — tombstones rows (``alive`` mask), purges
    the dead targets out of every *affected* neighbor list with the
    chunked ``knn_compact`` kernel, and refills the holes of affected rows
    from their surviving neighbors' lists (one friend-of-a-friend merge
    round).

  * ``MutableKNNStore`` — capacity-doubling padded arrays (features,
    squared norms, neighbor lists, alive mask). Shapes only change on a
    doubling, so the jitted insert/delete/search computations are reused
    across steady-state streaming updates instead of recompiling per call.

**Frontier compaction.** Every update step operates on an explicit,
compacted frontier of affected row ids instead of masking over the dense
store: the frontier (``graph_search.expand_frontier`` for inserts, a
dead-edge scan for deletes) is gathered into padded chunks of
``OnlineConfig.chunk`` rows, the merge/compact kernels run per chunk
(``kernels.ops.knn_merge_rows`` / ``knn_compact_rows``), and results are
scattered back. Update cost therefore scales with the frontier size, not
the store size — the friend-of-a-friend principle says a localized change
only propagates along a small frontier, so stores can grow past 10^5 rows
without updates going dense. The only O(n) work left per update is
bitwise mask bookkeeping (no distance evaluations). Setting
``OnlineConfig(frontier=False)`` keeps the same semantics but puts every
allocated row on the delete frontier — the dense baseline used by
``benchmarks/bench_online.py`` to measure the compaction win.

Cost accounting mirrors the offline build: both entry points return a
``DescentStats`` whose ``dist_evals`` counts (an upper bound on) distance
evaluations, and whose ``frontier_rows`` / ``padded_rows`` record how many
store rows the update actually touched (see ``tests/test_online.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp

from repro.core import faults, heap, quantize
from repro.core import metric as metric_mod
from repro.core.graph_search import SearchConfig, expand_frontier, graph_search
from repro.core.heap import NeighborLists
from repro.core.layout import pad_features
from repro.core.quantize import QuantizedStore
from repro.core.nn_descent import (
    DescentConfig,
    DescentStats,
    build_knn_graph,
    compact_pairs,
    invert_candidates,
)
from repro.core.router import (
    Router,
    RouterConfig,
    build_router,
    needs_rebuild,
    router_delete,
    router_insert,
)
from repro.core.spans import span
from repro.kernels import ops

_FILL = 1e6   # coordinate fill for unallocated rows (cf. layout.pad_points)


@dataclasses.dataclass(frozen=True)
class OnlineConfig:
    beam: int = 32            # seeding graph-search pool width
    seed_rounds: int = 24     # seeding graph-search expansion budget
    seed_expand: int = 4      # fused search: pool nodes expanded per round
                              # (SearchConfig.expand for seeding + queries)
    q_block: int = 256        # fused search: queries per block (the
                              # serving-side compile-once quantum; see
                              # serve/scheduler.py knn_q_block plumbing)
    refine_rounds: int = 2    # localized friend-of-a-friend rounds
    self_join: bool = True    # all-pairs join within the inserted batch
    self_join_max: int = 512  # skip the O(m^2) self-join beyond this m
    merge_mult: int = 2       # reverse-merge buffer = merge_mult * k
    backend: str = "auto"     # kernel dispatch for the chunked
                              # merge/compact kernels (ops.knn_merge_rows /
                              # ops.knn_compact_rows)
    chunk: int = 1024         # frontier chunk: padded row-id buffers are
                              # rounded up to a multiple of this, and the
                              # delete path processes one chunk at a time
    frontier: bool = True     # False = dense baseline: every allocated row
                              # goes on the delete frontier (bench only)
    frontier_mult: int = 4    # insert reverse-frontier cap, in units of
                              # m*k (the 2-hop closure is truncated to
                              # min(cap, frontier_mult*m*k) rows)
    route_src: int = 0        # fused reverse routing: per-receiver
                              # source-incidence buffer (0 = 2*merge_mult*k;
                              # overflow is dropped — bounded-buffer
                              # sampling noise, cf. DescentConfig.join_src)
    metric: str = "l2"        # l2 | cosine | mips — the store keeps its
                              # rows in the metric's l2-equivalent form
                              # (core/metric.py: cosine rows normalized,
                              # mips rows augmented d -> d+1 with the
                              # bound in MutableKNNStore.mips_m), applied
                              # once where rows enter (from_graph /
                              # knn_insert) so the kernels, the quantized
                              # mirror, and the router all work per
                              # metric unchanged. Searches transform
                              # queries per batch; distances come back
                              # transformed-space l2 (monotone in the
                              # native metric).
    precision: str = "f32"    # f32 | bf16 | int8 — the store keeps a
                              # quantized mirror (core/quantize.py) that
                              # candidate SCORING reads on the query and
                              # insert-seeding search paths (two-stage:
                              # the final pool re-ranks fp32, so returned
                              # distances stay exact). The mirror updates
                              # incrementally with inserts and grows with
                              # the capacity doubling; the localized
                              # refinement joins stay fp32 (they touch
                              # O(frontier) rows — bandwidth is not their
                              # bottleneck; the graph's stored distances
                              # stay exact for free).
    router: RouterConfig | None = None
                              # coarse routing layer (core/router.py):
                              # when set, the store keeps a centroid
                              # router that seeds every search with
                              # hierarchical entry points, maintained
                              # incrementally on insert/delete and
                              # rebuilt lazily past the drift threshold.
                              # Frozen (hashable) — OnlineConfig is a
                              # static jit argument of the stitch path.


@dataclasses.dataclass(frozen=True)
class MutableKNNStore:
    """Growable K-NN graph store. Rows [0, n) are allocated; ``alive``
    marks the live ones (False = tombstoned or unallocated)."""

    x: jax.Array          # (cap, dp) feature-padded points, stored in
                          # cfg.metric's l2-equivalent transformed form
    x2: jax.Array         # (cap,) cached squared norms
    nl: NeighborLists     # (cap, k) bounded neighbor lists
    alive: jax.Array      # (cap,) bool
    n: int                # allocation high-water mark
    d: int                # logical RAW feature dim (what callers hand
                          # insert/search; mips stores d+1 internally)
    cfg: OnlineConfig
    qs: QuantizedStore | None = None  # quantized mirror of ``x``
                                      # (cfg.precision != "f32" only)
    router: Router | None = None      # coarse routing layer
                                      # (cfg.router is not None only)
    mips_m: float = 0.0   # mips augmentation bound M (cfg.metric="mips"
                          # only; echoed/validated by core/persist.py).
                          # Set at build, or at the FIRST insert of a
                          # store that started empty; later inserts
                          # share it (over-norm rows clamp + warn).

    @property
    def capacity(self) -> int:
        return self.x.shape[0]

    @property
    def k(self) -> int:
        return self.nl.idx.shape[1]

    @property
    def graph_idx(self) -> jax.Array:
        return self.nl.idx

    def live_count(self) -> int:
        return int(jnp.sum(self.alive))

    @classmethod
    def from_graph(
        cls,
        x: jax.Array,
        dist: jax.Array,
        idx: jax.Array,
        *,
        cfg: OnlineConfig | None = None,
    ) -> "MutableKNNStore":
        """Wrap an offline ``build_knn_graph`` result (original id
        space). ``x`` is the RAW (untransformed) corpus: under
        cfg.metric the same reduction the build applied is applied here
        (same rows, same mips bound M), so the stored rows match the
        graph's transformed-space distances exactly."""
        cfg = cfg or OnlineConfig()
        n, d = x.shape
        x, mips_m = metric_mod.transform_corpus(x, cfg.metric)
        xp = pad_features(x.astype(jnp.float32))
        cap = _next_capacity(n)
        store = cls(
            x=jnp.full((cap, xp.shape[1]), _FILL, jnp.float32).at[:n].set(xp),
            x2=jnp.zeros((cap,), jnp.float32),
            nl=NeighborLists(
                jnp.full((cap, idx.shape[1]), jnp.inf, jnp.float32)
                .at[:n].set(dist.astype(jnp.float32)),
                jnp.full((cap, idx.shape[1]), -1, jnp.int32)
                .at[:n].set(idx.astype(jnp.int32)),
                jnp.zeros((cap, idx.shape[1]), bool),
            ),
            alive=jnp.zeros((cap,), bool).at[:n].set(True),
            n=n,
            d=d,
            cfg=cfg,
            mips_m=mips_m,
        )
        store = dataclasses.replace(
            store, x2=jnp.sum(store.x * store.x, axis=1)
        )
        if cfg.precision != "f32":
            store = dataclasses.replace(
                store,
                qs=quantize.quantize_corpus(
                    store.x, cfg.precision,
                    # the mirror's logical dim is the TRANSFORMED one
                    # (mips appends a coordinate) — x was reduced above
                    width=quantize.mirror_width(x.shape[1],
                                                store.x.shape[1]),
                ),
            )
        if cfg.router is not None:
            store = dataclasses.replace(
                store,
                router=build_router(
                    store.x, cfg=cfg.router, key=jax.random.key(29),
                    alive=store.alive, x2=store.x2, backend=cfg.backend,
                ),
            )
        return store

    @classmethod
    def empty(
        cls,
        d: int,
        *,
        k: int = 20,
        cfg: OnlineConfig | None = None,
    ) -> "MutableKNNStore":
        """A store with no rows: every search answers empty (+inf/-1)
        and the first ``knn_insert`` acts as a first build (all seeds
        miss, so the batch self-join links the graph). A configured
        router attaches lazily via ``ensure_router`` once rows exist —
        there is nothing to cluster yet. Under cfg.metric="mips" the
        augmentation bound M is unknown until rows exist — the first
        ``knn_insert`` sets ``mips_m`` from its batch."""
        cfg = cfg or OnlineConfig()
        d_t = metric_mod.transformed_dim(d, cfg.metric)
        dp = pad_features(jnp.zeros((1, d_t), jnp.float32)).shape[1]
        store = cls(
            x=jnp.full((8, dp), _FILL, jnp.float32),
            x2=jnp.full((8,), dp * _FILL * _FILL, jnp.float32),
            nl=NeighborLists(
                jnp.full((8, k), jnp.inf, jnp.float32),
                jnp.full((8, k), -1, jnp.int32),
                jnp.zeros((8, k), bool),
            ),
            alive=jnp.zeros((8,), bool),
            n=0,
            d=d,
            cfg=cfg,
        )
        if cfg.precision != "f32":
            store = dataclasses.replace(
                store,
                qs=quantize.quantize_corpus(
                    store.x, cfg.precision,
                    width=quantize.mirror_width(d_t, dp),
                ),
            )
        return store

    @classmethod
    def build(
        cls,
        x: jax.Array,
        k: int = 20,
        *,
        cfg: OnlineConfig | None = None,
        descent: DescentConfig | None = None,
        key: jax.Array | None = None,
    ) -> tuple["MutableKNNStore", DescentStats]:
        """Offline build + wrap. Returns (store, build stats). ``x`` is
        RAW rows; ``cfg.metric`` propagates into the DescentConfig so
        the build and the store apply the same reduction (each to the
        raw input, exactly once)."""
        cfg = cfg or OnlineConfig()
        dcfg = descent or DescentConfig(k=k, rho=1.0, max_iters=15)
        if dcfg.k != k:
            dcfg = dataclasses.replace(dcfg, k=k)
        if dcfg.metric != cfg.metric:
            dcfg = dataclasses.replace(dcfg, metric=cfg.metric)
        dist, idx, stats = build_knn_graph(x, k=k, cfg=dcfg, key=key)
        return cls.from_graph(x, dist, idx, cfg=cfg), stats

    def search(
        self,
        queries: jax.Array,
        *,
        k_out: int = 10,
        beam: int = 32,
        rounds: int = 24,
        key: jax.Array | None = None,
        cfg: SearchConfig | None = None,
        filter_ids: jax.Array | None = None,
    ):
        """Batched query path: fused blocked graph search that never
        returns a tombstoned or unallocated row. The store's cached norm
        vector is passed through (no per-call x2 recomputation); ``cfg``
        overrides the default SearchConfig built from the kwargs and the
        store's backend / expansion / query-block knobs (its ``metric``
        is always forced to the store's — rows are stored transformed,
        searching them under another metric would be silent garbage).

        Queries come in RAW (store.d features, any metric) and are
        reduced here/in graph_search; returned distances are
        transformed-space squared l2 (metric.similarity_from_dist
        converts back). ``filter_ids`` is a per-call predicate mask —
        (rows,) shared or (q, rows) per query, sized to ``store.n`` or
        the full capacity (shorter masks are False-padded: unallocated
        rows are inadmissible anyway) — filtered rows are never
        returned, exactly like tombstones."""
        with span("store.search", queries=len(queries)):
            if cfg is None:
                cfg = SearchConfig(
                    beam=beam, rounds=rounds, expand=self.cfg.seed_expand,
                    q_block=self.cfg.q_block, backend=self.cfg.backend,
                    precision=self.cfg.precision,
                )
            if cfg.metric != self.cfg.metric:
                cfg = dataclasses.replace(cfg, metric=self.cfg.metric)
            if filter_ids is not None:
                filter_ids = jnp.asarray(filter_ids, bool)
                short = self.capacity - filter_ids.shape[-1]
                if short > 0:
                    pad = [(0, 0)] * (filter_ids.ndim - 1) + [(0, short)]
                    filter_ids = jnp.pad(filter_ids, pad,
                                         constant_values=False)
            q = _pad_to(metric_mod.transform_queries(queries, self.cfg.metric),
                        self.x.shape[1])
            return graph_search(
                self.x, self.nl.idx, q, k_out=k_out, key=key,
                alive=self.alive, x2=self.x2, cfg=cfg, qstore=self.qs,
                router=self.router, filter_ids=filter_ids,
            )


def _next_capacity(n: int) -> int:
    cap = 8
    while cap < n:
        cap *= 2
    return cap


def _ceil_chunk(f: int, chunk: int, cap: int) -> int:
    """Round a frontier size up to whole padded chunks, capped at cap."""
    return min(cap, ((max(f, 1) + chunk - 1) // chunk) * chunk)


def _pad_to(x: jax.Array, dp: int) -> jax.Array:
    xp = pad_features(x.astype(jnp.float32))
    if xp.shape[1] != dp:
        raise ValueError(
            f"feature dim {x.shape[1]} pads to {xp.shape[1]}, store has {dp}"
        )
    return xp


def _grown(store: MutableKNNStore, need: int) -> MutableKNNStore:
    """Double capacity until ``need`` rows fit (amortized O(1) growth;
    shapes change only on a doubling so jitted update steps are reused)."""
    cap = store.capacity
    if need <= cap:
        return store
    new_cap = cap
    while new_cap < need:
        new_cap *= 2
    pad = new_cap - cap
    k = store.k
    dp = store.x.shape[1]
    return dataclasses.replace(
        store,
        qs=(None if store.qs is None
            else quantize.grow(store.qs, new_cap, _FILL)),
        router=(None if store.router is None
                else store.router._replace(assign=jnp.concatenate(
                    [store.router.assign,
                     jnp.full((pad,), -1, jnp.int32)]
                ))),
        x=jnp.concatenate(
            [store.x, jnp.full((pad, dp), _FILL, jnp.float32)]
        ),
        x2=jnp.concatenate(
            [store.x2, jnp.full((pad,), dp * _FILL * _FILL, jnp.float32)]
        ),
        nl=NeighborLists(
            jnp.concatenate(
                [store.nl.dist, jnp.full((pad, k), jnp.inf, jnp.float32)]
            ),
            jnp.concatenate(
                [store.nl.idx, jnp.full((pad, k), -1, jnp.int32)]
            ),
            jnp.concatenate([store.nl.new, jnp.zeros((pad, k), bool)]),
        ),
        alive=jnp.concatenate([store.alive, jnp.zeros((pad,), bool)]),
    )


def _frontier_slots(fids: jax.Array, recv: jax.Array) -> jax.Array:
    """Map receiver row ids into frontier-local slots. ``fids`` is an
    ascending padded id buffer (expand_frontier's layout: valid prefix,
    -1 tail); receivers not on the frontier map to -1 (dropped)."""
    big = jnp.iinfo(jnp.int32).max
    fs = jnp.where(fids >= 0, fids, big)
    slot = jnp.searchsorted(fs, recv)
    slot_c = jnp.clip(slot, 0, fids.shape[0] - 1)
    hit = (recv >= 0) & (fs[slot_c] == recv)
    return jnp.where(hit, slot_c.astype(jnp.int32), -1)


def _route_reverse(
    nl: NeighborLists,
    fids: jax.Array,       # (f,) frontier row-id buffer (ascending, -1 tail)
    recv: jax.Array,       # (m, w) receiver ids per source row (-1 invalid)
    dd: jax.Array,         # (m, w) pair distances (+inf on invalid)
    src_ids: jax.Array,    # (m,) source (new point) row ids
    c: int,                # candidate width handed to the frontier merge
    s_cap: int,            # per-receiver source-incidence buffer
    backend: str,
    prefilter: bool,
):
    """Fused reverse-edge routing (the online face of the knn_join kernel
    family): instead of pushing all (receiver, source, dist) pairs through
    a (receiver, dist) lexsort (``compact_pairs``), each frontier receiver
    inverts its incidences, gathers its incoming distances, and the
    ``knn_join_select`` kernel reduces them to the best ``c`` under the
    receiver's k-th-distance prefilter. Returns (f, c) candidate buffers
    aligned with ``fids`` for heap.merge_rows."""
    f = fids.shape[0]
    m, w = recv.shape
    lrecv = _frontier_slots(fids, recv.reshape(-1)).reshape(m, w)
    # overflow keeps the closest incoming edges per receiver, not the
    # smallest (row, slot) — hub receivers on hub-heavy inserts no longer
    # systematically drop late sources
    rows_of, slot_of = invert_candidates(lrecv, f, s_cap, prio=dd)
    ok = rows_of >= 0
    lin = jnp.where(ok, rows_of * w + slot_of, 0)
    gd = jnp.where(ok, dd.reshape(-1)[lin], jnp.inf)        # (f, s_cap)
    gi = jnp.where(ok, src_ids[jnp.where(ok, rows_of, 0)], -1)
    if prefilter:
        safe = jnp.where(fids >= 0, fids, 0)
        kth = jnp.where(fids >= 0, nl.dist[safe, -1], 0.0)
    else:
        kth = jnp.full((f,), jnp.inf)
    return ops.knn_join_select(gd, gi, kth, c=c, backend=backend)


# ---------------------------------------------------------------------------
# insert
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def _insert_stitch(
    x: jax.Array,
    x2: jax.Array,
    nl: NeighborLists,
    alive: jax.Array,
    q: jax.Array,          # (m, dp) new points
    ids: jax.Array,        # (m,) their row ids
    seed_d: jax.Array,     # (m, k) graph-search seed distances
    seed_i: jax.Array,     # (m, k) graph-search seed ids
    cfg: OnlineConfig,
):
    """Stitch m new rows into the graph and run the localized refinement.

    All reverse-edge repair runs on a compacted frontier: the 1-hop
    closure of the new rows for the seed merge, the 2-hop closure per
    refinement round — gathered into padded chunks and merged with the
    chunked kernels, never a dense pass over the store.

    Returns (x, x2, nl, alive, extra dist evals, per-round accepted,
    frontier rows touched, padded rows processed)."""
    cap, k = nl.idx.shape
    m = ids.shape[0]
    c = cfg.merge_mult * k
    chunk = max(1, min(cfg.chunk, cap))
    q2 = jnp.sum(q * q, axis=1)

    x = x.at[ids].set(q)
    x2 = x2.at[ids].set(q2)
    alive = alive.at[ids].set(True)
    seed_ok = seed_i >= 0
    nl = NeighborLists(
        nl.dist.at[ids].set(jnp.where(seed_ok, seed_d, jnp.inf)),
        nl.idx.at[ids].set(jnp.where(seed_ok, seed_i, -1)),
        nl.new.at[ids].set(seed_ok),
    )

    evals = jnp.zeros((), jnp.int32)
    f_rows = jnp.zeros((), jnp.int32)
    p_rows = jnp.zeros((), jnp.int32)
    upds = []

    # reverse-merge the seed edges: each new point is a candidate for the
    # rows that seeded it (distances already evaluated by the search).
    # Receivers all sit on the 1-hop closure of the new rows, which fits
    # exactly in m*(k+1) frontier slots — no truncation.
    f_seed = _ceil_chunk(min(cap, m * (k + 1)), chunk, cap)
    s_cap = cfg.route_src or 2 * c
    fids, _ = expand_frontier(nl.idx, ids, hops=1, capacity=f_seed)
    cd, ci = _route_reverse(
        nl, fids, jnp.where(seed_ok, seed_i, -1),
        jnp.where(seed_ok, seed_d, jnp.inf), ids, c, s_cap,
        cfg.backend, prefilter=False,
    )
    nl, upd0 = heap.merge_rows(nl, fids, cd, ci, backend=cfg.backend)
    upds.append(jnp.sum(upd0))
    f_rows += jnp.sum(fids >= 0, dtype=jnp.int32)
    p_rows += f_seed

    # all-pairs join within the inserted batch: a streamed batch is often
    # self-similar (new points are each other's nearest neighbors) and the
    # seed search only sees pre-existing rows
    if cfg.self_join and 1 < m <= cfg.self_join_max:
        d_qq = q2[:, None] + q2[None, :] - 2.0 * (
            q @ q.T
        )
        off = ~jnp.eye(m, dtype=bool)
        d_qq = jnp.where(off, jnp.maximum(d_qq, 0.0), jnp.inf)
        cand = jnp.where(off, jnp.broadcast_to(ids[None, :], (m, m)), -1)
        nl, upd_sj = heap.merge_rows(nl, ids, d_qq, cand,
                                     backend=cfg.backend)
        evals += m * (m - 1) // 2
        upds[-1] = upds[-1] + jnp.sum(upd_sj)
        f_rows += m
        p_rows += m

    # localized NN-Descent: friend-of-a-friend rounds over the frontier
    f_rev = _ceil_chunk(min(cap, cfg.frontier_mult * m * k), chunk, cap)
    for _r in range(cfg.refine_rounds):
        ni = nl.idx[ids]                                    # (m, k)
        nb = nl.idx[jnp.clip(ni, 0, cap - 1)]               # (m, k, k)
        # receivers of this round's reverse edges all sit on the 2-hop
        # closure of the new rows (cand = neighbors-of-neighbors); the
        # frontier buffer is that closure, truncated to f_rev rows
        fids_r, _ = expand_frontier(
            nl.idx, ids, hops=2, capacity=f_rev, alive=alive
        )
        cand = nb.reshape(m, k * k)
        src_ok = jnp.broadcast_to(
            (ni >= 0)[:, :, None], (m, k, k)
        ).reshape(m, k * k)
        ok = (
            src_ok
            & (cand >= 0)
            & alive[jnp.clip(cand, 0, cap - 1)]
            & (cand != ids[:, None])
        )
        ok &= ~(cand[:, :, None] == ni[:, None, :]).any(-1)  # already linked
        cx = x[jnp.clip(cand, 0, cap - 1)]                   # (m, kk, dp)
        dd = q2[:, None] + x2[jnp.clip(cand, 0, cap - 1)] - 2.0 * jnp.einsum(
            "md,mcd->mc", q, cx, preferred_element_type=jnp.float32
        )
        dd = jnp.where(ok, jnp.maximum(dd, 0.0), jnp.inf)
        evals += jnp.sum(ok, dtype=jnp.int32)

        # forward: candidates into the new rows' lists
        nl, upd_f = heap.merge_rows(
            nl, ids, dd, jnp.where(ok, cand, -1), backend=cfg.backend
        )

        # reverse: the new point is a candidate for every touched row that
        # it beats (receiver-side prefilter, applied inside the fused
        # select kernel — as in nn_descent's local_join_fused)
        cd, ci = _route_reverse(
            nl, fids_r, jnp.where(ok, cand, -1), dd, ids, c, s_cap,
            cfg.backend, prefilter=True,
        )
        nl, upd_r = heap.merge_rows(nl, fids_r, cd, ci, backend=cfg.backend)
        upds.append(jnp.sum(upd_f) + jnp.sum(upd_r))
        # count rows actually on the compacted buffer (the closure may be
        # truncated to f_rev, and truncated rows are never touched)
        f_rows += m + jnp.sum(fids_r >= 0, dtype=jnp.int32)
        p_rows += m + f_rev

    return x, x2, nl, alive, evals, jnp.stack(upds), f_rows, p_rows


def knn_insert(
    store: MutableKNNStore,
    new_points: jax.Array,
    *,
    key: jax.Array | None = None,
) -> tuple[MutableKNNStore, DescentStats]:
    """Insert ``new_points`` (m, d) into the store. Deterministic given
    ``key`` (the only randomness is the seed search's entry points).

    ``new_points`` are RAW rows; the store's metric reduction is applied
    here (cosine: normalize; mips: augment with the store's bound
    ``mips_m`` — rows that outgrow it clamp with a RuntimeWarning, and a
    store that started ``empty`` sets the bound from its first batch),
    so the seeding search, the FoaF refinement, the quantized mirror
    update and the router maintenance below all run metric-unchanged.

    Returns (store, stats); ``stats.dist_evals`` is an upper bound on the
    distance evaluations spent (the seed-search term is the analytic bound
    beam + rounds*k per query; the refinement term is exact).
    """
    cfg = store.cfg
    k = store.k
    m = int(new_points.shape[0])
    if m == 0:
        return store, DescentStats(iters=0, dist_evals=0)
    key = jax.random.key(0) if key is None else key
    if new_points.shape[1] != store.d:
        raise ValueError(
            f"new points have dim {new_points.shape[1]}, store has {store.d}"
        )
    mips_m = store.mips_m
    if cfg.metric == "mips" and store.n == 0 and mips_m == 0.0:
        # a store built via ``empty`` has no bound yet — its first batch
        # defines M (later batches share it, clamping past it)
        mips_m = metric_mod.mips_max_norm(new_points)
        store = dataclasses.replace(store, mips_m=mips_m)
    new_t, _ = metric_mod.transform_corpus(
        new_points, cfg.metric, mips_m=mips_m if cfg.metric == "mips"
        else None)
    q = _pad_to(new_t, store.x.shape[1])
    store = _grown(store, store.n + m)
    ids = jnp.arange(store.n, store.n + m, dtype=jnp.int32)

    beam = max(cfg.beam, k)
    scfg = SearchConfig(
        beam=beam, rounds=cfg.seed_rounds, expand=cfg.seed_expand,
        q_block=cfg.q_block, backend=cfg.backend,
        precision=cfg.precision, metric=cfg.metric,
    )
    seed_d, seed_i = graph_search(
        store.x, store.nl.idx, q, k_out=k, key=key, alive=store.alive,
        x2=store.x2, cfg=scfg, qstore=store.qs, router=store.router,
    )
    # analytic eval bound: beam entry distances + k per expanded node (the
    # fused path expands in chunks of seed_expand, so round the budget up
    # to whole rounds; backend="ref" expands exactly seed_rounds nodes);
    # a quantized seeding search re-ranks its final pool fp32 — beam more
    scfg_quant = scfg.precision != "f32" and scfg.backend != "ref"
    seed_evals = m * ((2 if scfg_quant else 1) * beam
                     + (cfg.seed_rounds if cfg.backend == "ref"
                        else scfg.n_rounds * cfg.seed_expand) * k)

    x, x2, nl, alive, evals, upds, f_rows, p_rows = _insert_stitch(
        store.x, store.x2, store.nl, store.alive, q, ids, seed_d, seed_i,
        cfg,
    )
    qs = store.qs if store.qs is None else quantize.update_rows(
        store.qs, ids, q
    )
    router = store.router
    if router is not None:
        router = router_insert(router, ids, q, backend=cfg.backend)
        router = _maybe_rebuild_router(
            router, x, x2, alive, cfg, jax.random.fold_in(key, 911)
        )
    stats = DescentStats(
        iters=cfg.refine_rounds,
        dist_evals=seed_evals + int(evals),
        updates=tuple(int(u) for u in upds),
        frontier_rows=int(f_rows),
        padded_rows=int(p_rows),
    )
    return (
        dataclasses.replace(
            store, x=x, x2=x2, nl=nl, alive=alive, n=store.n + m, qs=qs,
            router=router,
        ),
        stats,
    )


def _maybe_rebuild_router(
    router: Router,
    x: jax.Array,
    x2: jax.Array,
    alive: jax.Array,
    cfg: OnlineConfig,
    key: jax.Array,
) -> Router:
    """Lazy drift rebuild: incremental maintenance keeps the router exact
    w.r.t. assignments/members, but the CENTROIDS slowly stop describing
    the data as the corpus churns — past the drift threshold, refit.

    A failed rebuild (an ``OSError`` — the ``router.rebuild`` fault site
    models the I/O-class failures a refit can hit) degrades, never
    crashes: the incremental router is stale but still *correct* as an
    entry-point heuristic (holes fall back to random draws inside
    graph_search), so the store keeps serving from it — degraded recall
    beats a dead insert path. The rebuild is re-attempted on the next
    insert that crosses the threshold. Any other error (a compile or
    device failure inside ``build_router``) propagates."""
    rcfg = cfg.router or RouterConfig()
    if needs_rebuild(router, int(jnp.sum(alive)), rcfg):
        try:
            faults.maybe_raise("router.rebuild")
            return build_router(
                x, cfg=rcfg, key=key, alive=alive, x2=x2,
                backend=cfg.backend,
            )
        except OSError as e:
            warnings.warn(
                f"router rebuild failed ({e}); serving continues from "
                "the stale router", RuntimeWarning, stacklevel=2)
    return router


def ensure_router(
    store: MutableKNNStore,
    rcfg: RouterConfig | None = None,
    *,
    key: jax.Array | None = None,
) -> MutableKNNStore:
    """Idempotently attach a router to an existing store (serving-side
    plumbing: ContinuousBatcher / MutableKNNDatastore opt in without
    rebuilding the store). The router clusters the store's TRANSFORMED
    rows, so routed entries are correct under any ``cfg.metric`` with
    no per-metric routing code."""
    if store.router is not None:
        return store
    rcfg = rcfg or store.cfg.router or RouterConfig()
    return dataclasses.replace(
        store,
        cfg=dataclasses.replace(store.cfg, router=rcfg),
        router=build_router(
            store.x, cfg=rcfg,
            key=jax.random.key(29) if key is None else key,
            alive=store.alive, x2=store.x2, backend=store.cfg.backend,
        ),
    )


# ---------------------------------------------------------------------------
# delete
# ---------------------------------------------------------------------------


@jax.jit
def _delete_need(idx: jax.Array, alive: jax.Array) -> jax.Array:
    """Rows needing compaction after a tombstone: rows that reference a
    dead id, plus newly-dead rows that still hold a list. One O(n*k)
    bitwise scan — no distance evaluations; everything downstream runs on
    the compacted frontier this mask defines."""
    cap = alive.shape[0]
    valid = idx >= 0
    dead_tgt = valid & ~alive[jnp.clip(idx, 0, cap - 1)]
    return dead_tgt.any(axis=1) | (valid.any(axis=1) & ~alive)


@functools.partial(jax.jit, static_argnames=("backend",))
def _purge_chunk(
    nl: NeighborLists,
    rows: jax.Array,
    alive: jax.Array,
    backend: str,
):
    """One padded chunk of the tombstone purge (heap.purge_rows)."""
    return heap.purge_rows(nl, rows, alive, backend=backend)


@functools.partial(jax.jit, static_argnames=("backend",))
def _refill_chunk(
    x: jax.Array,
    x2: jax.Array,
    nl: NeighborLists,
    idx0: jax.Array,       # (cap, k) post-purge snapshot (read-only)
    alive: jax.Array,
    rows: jax.Array,       # (chunk,) frontier row ids, -1 = padding
    removed: jax.Array,    # (chunk,) per-row purge removal count
    backend: str,
):
    """Refill one padded chunk of affected rows from their surviving
    neighbors' lists (one friend-of-a-friend round). Candidate reads come
    from the post-purge snapshot ``idx0`` so chunk processing order cannot
    change the result (all chunks see the same graph state).

    Returns (nl, dist evals, accepted, orphan count in this chunk)."""
    cap, k = nl.idx.shape
    f = rows.shape[0]
    ok_row = rows >= 0
    safe = jnp.where(ok_row, rows, 0)
    refill = ok_row & alive[safe] & (removed > 0)

    ni = idx0[safe]                                        # (f, k)
    nb = idx0[jnp.clip(ni, 0, cap - 1)].reshape(f, k * k)
    src_ok = jnp.broadcast_to(
        (ni >= 0)[:, :, None], (f, k, k)
    ).reshape(f, k * k)
    ok = (
        refill[:, None]
        & src_ok
        & (nb >= 0)
        & alive[jnp.clip(nb, 0, cap - 1)]
        & (nb != safe[:, None])
    )
    ok &= ~(nb[:, :, None] == ni[:, None, :]).any(-1)
    cx = x[jnp.clip(nb, 0, cap - 1)]
    dd = x2[safe][:, None] + x2[jnp.clip(nb, 0, cap - 1)] - 2.0 * jnp.einsum(
        "fd,fcd->fc", x[safe], cx, preferred_element_type=jnp.float32
    )
    dd = jnp.where(ok, jnp.maximum(dd, 0.0), jnp.inf)
    evals = jnp.sum(ok, dtype=jnp.int32)
    nl, upd = heap.merge_rows(
        nl, rows, dd, jnp.where(ok, nb, -1), backend=backend
    )

    orphan = ok_row & alive[safe] & ~(nl.idx[safe] >= 0).any(axis=1)
    return nl, evals, jnp.sum(upd), jnp.sum(orphan, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("merge_c",))
def _reconnect_orphans(
    x: jax.Array,
    x2: jax.Array,
    nl: NeighborLists,
    alive: jax.Array,
    merge_c: int,
):
    """Reconnect orphans: a live row whose ENTIRE neighborhood died has no
    surviving neighbors to refill from (and its inbound edges were purged
    too) — re-anchor it to k deterministic live rows, both directions, so
    it stays reachable by graph search. Rare (requires a whole
    neighborhood to die at once), so this runs as a separate pass only
    when a refill chunk reports orphans."""
    cap, k = nl.idx.shape
    rows = jnp.arange(cap, dtype=jnp.int32)
    orphan = alive & ~(nl.idx >= 0).any(axis=1)
    anchor_score = jnp.where(alive & ~orphan, (cap - rows).astype(jnp.float32),
                             -1.0)
    _, anchors = jax.lax.top_k(anchor_score, k)          # lowest live ids
    ok2 = (
        orphan[:, None]
        & alive[anchors][None, :]
        & ~orphan[anchors][None, :]
        & (anchors[None, :] != rows[:, None])
    )
    dd2 = x2[:, None] + x2[anchors][None, :] - 2.0 * (
        x @ x[anchors].T
    )
    dd2 = jnp.where(ok2, jnp.maximum(dd2, 0.0), jnp.inf)
    evals = jnp.sum(ok2, dtype=jnp.int32)
    anc = jnp.broadcast_to(anchors[None, :], (cap, k))
    nl, upd2 = heap.merge(nl, dd2, jnp.where(ok2, anc, -1))
    # reverse edges: the anchors adopt the orphan so it is reachable.
    # This cold path keeps compact_pairs (exact by-distance truncation):
    # every orphan targets the SAME k anchors, so the per-receiver
    # in-degree is unbounded and a bounded source buffer could drop the
    # closest orphans — the fused routing's contract doesn't fit here.
    recv = jnp.where(ok2, anc, -1).reshape(-1)
    src = jnp.broadcast_to(rows[:, None], (cap, k)).reshape(-1)
    cd, ci = compact_pairs(recv, src, dd2.reshape(-1), cap, merge_c)
    nl, upd3 = heap.merge(nl, cd, ci)
    return nl, evals, jnp.sum(upd2) + jnp.sum(upd3)


def knn_delete(
    store: MutableKNNStore,
    ids: jax.Array,
) -> tuple[MutableKNNStore, DescentStats]:
    """Tombstone ``ids`` and patch every neighbor list that pointed at
    them. Deleted rows are never returned by ``store.search`` and never
    re-enter any list; their slots are not reused (capacity is monotone).

    The purge + refill run over the compacted frontier of affected rows
    (rows referencing a dead id, plus the dead rows themselves), gathered
    into ``cfg.chunk``-row padded chunks — O(frontier) work, not O(n).
    With ``cfg.frontier=False`` every allocated row is processed (the
    dense baseline; identical results).

    Metric/filter behavior: refill distances are computed over the
    store's already-transformed rows, so deletion is metric-correct
    with no per-metric code; downstream, a tombstoned row exits every
    search exactly like a filtered one (id -1 -> +inf in the kernel
    epilogue) — ``filter_ids`` masks compose with tombstones, they do
    not replace them.
    """
    cfg = store.cfg
    ids = jnp.asarray(ids, jnp.int32)
    alive = store.alive.at[ids].set(False)
    cap = store.capacity
    chunk = max(1, min(cfg.chunk, cap))

    router = store.router
    if router is not None:
        # the alive mask changed on EVERY return path below — maintain
        # the router here, before the early no-frontier exit
        router = router_delete(router, ids, alive, backend=cfg.backend)
        router = _maybe_rebuild_router(
            router, store.x, store.x2, alive, cfg,
            jax.random.fold_in(jax.random.key(31), int(ids.shape[0])),
        )

    if cfg.frontier:
        need = _delete_need(store.nl.idx, alive)
        f = int(jnp.sum(need))
        if f == 0:
            return (
                dataclasses.replace(store, alive=alive, router=router),
                DescentStats(iters=0, dist_evals=0, frontier_rows=0,
                             padded_rows=0),
            )
        n_chunks = (f + chunk - 1) // chunk
        fids = jnp.nonzero(
            need, size=n_chunks * chunk, fill_value=-1
        )[0].astype(jnp.int32)
    else:
        f = store.n
        n_chunks = (f + chunk - 1) // chunk
        ar = jnp.arange(n_chunks * chunk, dtype=jnp.int32)
        fids = jnp.where(ar < f, ar, -1)

    nl = store.nl
    removed = []
    for j in range(n_chunks):
        rows = jax.lax.dynamic_slice_in_dim(fids, j * chunk, chunk)
        nl, rm = _purge_chunk(nl, rows, alive, cfg.backend)
        removed.append(rm)

    idx0 = nl.idx      # post-purge snapshot: all refill chunks read this
    evals = jnp.zeros((), jnp.int32)
    upd = jnp.zeros((), jnp.int32)
    orphans = jnp.zeros((), jnp.int32)
    for j in range(n_chunks):
        rows = jax.lax.dynamic_slice_in_dim(fids, j * chunk, chunk)
        nl, ev, up, orp = _refill_chunk(
            store.x, store.x2, nl, idx0, alive, rows, removed[j],
            cfg.backend,
        )
        evals += ev
        upd += up
        orphans += orp

    if int(orphans) > 0:
        nl, ev2, up2 = _reconnect_orphans(
            store.x, store.x2, nl, alive, cfg.merge_mult * store.k
        )
        evals += ev2
        upd += up2

    stats = DescentStats(
        iters=1, dist_evals=int(evals), updates=(int(upd),),
        frontier_rows=f, padded_rows=n_chunks * chunk,
    )
    return (
        dataclasses.replace(store, nl=nl, alive=alive, router=router),
        stats,
    )
