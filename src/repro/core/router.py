"""Coarse routing layer: centroids + mini-graph for hierarchical entries.

Fixes the large-n recall collapse: uniform-random entry seeding strands
the fused beam far from the query (recall ~0.49 at n=1e5 where the 2k
smoke hits 0.96), and sharded serving replicated every query to every
shard. The router is a small k-means centroid set built with the repo's
own blocked l2 kernels, plus per-centroid member lists (nearest corpus
rows) and a tiny exact k-NN mini-graph over the centroids. Two roles:

- entry seeding: ``route_entries`` turns a query batch into per-query
  beam seeds — the nearest members of the query's top-t centroids —
  which ``graph_search`` uses instead of uniform-random draws.
- shard routing: ``graph_search_sharded`` uses centroid→shard affinity
  to dispatch each query to only the top-p shards (fan-out P → p).

The router lives alongside ``MutableKNNStore`` and is maintained
incrementally on insert/delete (assignment + member-list updates), with
a lazy full rebuild once accumulated drift passes ``rebuild_frac`` of
the live count.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import heap
from repro.core.nn_descent import compact_pairs
from repro.core.recall import brute_force_knn
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """Routing-layer knobs. Frozen/hashable: nested inside OnlineConfig,
    which is a static jit argument of the stitch/purge kernels."""
    n_centroids: int = 0       # 0 = auto: ~sqrt(live), clipped to [16, 1024]
    iters: int = 8             # Lloyd iterations (on the subsample)
    sample: int = 32768        # subsample size for the Lloyd fit
    members: int = 32          # member-list width per centroid
    graph_k: int = 8           # centroid mini-graph degree
    top_t: int = 4             # centroids probed per query at search time
    rebuild_frac: float = 0.25  # stale/live ratio that triggers a rebuild


class Router(NamedTuple):
    centroids: jax.Array        # (c, dp) f32, feature-padded like the store
    c2: jax.Array               # (c,) cached squared norms
    graph: jax.Array            # (c, g) i32 centroid mini-graph, -1 padded
    members: heap.NeighborLists  # (c, m) nearest corpus rows per centroid
    assign: jax.Array           # (cap,) i32 centroid per row, -1 = dead
    counts: jax.Array           # (c,) i32 live members per centroid
    stale: jax.Array            # () i32 mutations since last full build


def resolve_centroids(live: int, cfg: RouterConfig) -> int:
    if cfg.n_centroids > 0:
        return min(cfg.n_centroids, max(live, 1))
    return int(min(1024, max(16, round(max(live, 1) ** 0.5))))


@functools.partial(jax.jit, static_argnames=("c", "iters"))
def _lloyd(xs: jax.Array, c: int, iters: int, key: jax.Array) -> jax.Array:
    """Lloyd's k-means on the (already sampled) rows, seeded by k-means++:
    each seed after the first is drawn with probability proportional to
    its squared distance from the seeds so far, so well-separated
    clusters each get one. Seeding with the first c sampled rows left
    about a third of the clusters without a centroid when c is near
    their number (1 - 1/e coverage), and their queries then routed to
    the wrong shards. Empty clusters keep their previous centroid (the
    empty centroid simply attracts no entries)."""
    x2 = jnp.sum(xs * xs, 1)

    def sq_dist(v):
        return jnp.maximum(x2 - 2.0 * xs @ v + jnp.sum(v * v), 0.0)

    def seed(i, carry):
        cent, dmin = carry
        j = jax.random.categorical(jax.random.fold_in(key, i),
                                   jnp.log(jnp.maximum(dmin, 1e-30)))
        return cent.at[i].set(xs[j]), jnp.minimum(dmin, sq_dist(xs[j]))

    cent = jnp.zeros((c, xs.shape[1]), xs.dtype).at[0].set(xs[0])
    cent, _ = jax.lax.fori_loop(1, c, seed, (cent, sq_dist(xs[0])))

    def body(cent, _):
        d = jnp.maximum(
            jnp.sum(xs * xs, 1)[:, None]
            + jnp.sum(cent * cent, 1)[None, :]
            - 2.0 * xs @ cent.T,
            0.0,
        )
        a = jnp.argmin(d, axis=1)
        sums = jax.ops.segment_sum(xs, a, num_segments=c)
        cnt = jax.ops.segment_sum(
            jnp.ones((xs.shape[0],), jnp.float32), a, num_segments=c
        )
        new = jnp.where(
            cnt[:, None] > 0, sums / jnp.maximum(cnt, 1.0)[:, None], cent
        )
        return new, None

    cent, _ = jax.lax.scan(body, cent, None, length=iters)
    return cent


@functools.partial(jax.jit, static_argnames=("chunk", "backend"))
def _assign_all(x, x2, cent, c2, *, chunk: int = 4096, backend: str = "auto"):
    """Nearest centroid of every store row, chunked through the blocked
    distance tile. Returns ((cap,) dist, (cap,) idx)."""
    cap, dp = x.shape
    pad = (-cap) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    x2p = jnp.pad(x2, (0, pad))

    def one(args):
        xc, x2c = args
        d, i = ops.centroid_assign(xc, x2c, cent, c2, t=1, backend=backend)
        return d[:, 0], i[:, 0]

    d, i = jax.lax.map(
        one, (xp.reshape(-1, chunk, dp), x2p.reshape(-1, chunk))
    )
    return d.reshape(-1)[:cap], i.reshape(-1)[:cap]


def build_router(
    x: jax.Array,
    *,
    cfg: RouterConfig | None = None,
    key: jax.Array,
    alive: jax.Array | None = None,
    x2: jax.Array | None = None,
    backend: str = "auto",
) -> Router:
    """Fit centroids on a live subsample, assign every live row, compact
    per-centroid member lists, and build the exact centroid mini-graph.
    All distance work goes through the blocked l2 dispatch."""
    cfg = cfg or RouterConfig()
    cap = x.shape[0]
    x = x.astype(jnp.float32)
    if x2 is None:
        x2 = jnp.sum(x * x, axis=1)
    live = cap if alive is None else int(jnp.sum(alive))
    c = resolve_centroids(live, cfg)

    # keyed-top-k live subsample (dead rows weighted out); duplicated
    # tail rows when live < sample only add benign weight to Lloyd
    s = min(cfg.sample, cap)
    w = jax.random.uniform(key, (cap,))
    if alive is not None:
        w = jnp.where(alive, w, -1.0)
    wv, sample_ids = jax.lax.top_k(w, s)
    sample_ids = jnp.where(wv > 0.0, sample_ids, sample_ids[0])
    cent = _lloyd(x[sample_ids], min(c, s), cfg.iters,
                  jax.random.fold_in(key, 1))
    if cent.shape[0] < c:      # degenerate tiny corpus: pad with repeats
        cent = jnp.concatenate(
            [cent, jnp.broadcast_to(cent[:1], (c - cent.shape[0], cent.shape[1]))]
        )
    c2 = jnp.sum(cent * cent, axis=1)

    d_assign, assign = _assign_all(x, x2, cent, c2, backend=backend)
    if alive is not None:
        assign = jnp.where(alive, assign, -1)
        d_assign = jnp.where(alive, d_assign, jnp.inf)
    assign = assign.astype(jnp.int32)
    counts = (
        jnp.zeros((c,), jnp.int32)
        .at[jnp.clip(assign, 0, c - 1)]
        .add((assign >= 0).astype(jnp.int32))
    )

    m = min(cfg.members, cap)
    md, mi = compact_pairs(
        assign, jnp.arange(cap, dtype=jnp.int32), d_assign, c, m
    )
    members = heap.NeighborLists(md, mi, jnp.zeros_like(mi, dtype=bool))

    g = min(cfg.graph_k, c - 1)
    if g > 0:
        gd, gi = brute_force_knn(cent, cent, g, backend=backend)
        graph = jnp.where(jnp.isfinite(gd), gi, -1).astype(jnp.int32)
    else:
        graph = jnp.full((c, 1), -1, jnp.int32)

    return Router(
        centroids=cent, c2=c2, graph=graph, members=members,
        assign=assign, counts=counts, stale=jnp.zeros((), jnp.int32),
    )


def top_centroids(
    router: Router, queries: jax.Array, t: int, *, backend: str = "auto"
):
    """Top-t nearest centroids per query (exact — c is small by
    construction, <= ~1024, so one blocked tile beats a graph walk)."""
    q = queries.astype(jnp.float32)
    q2 = jnp.sum(q * q, axis=1)
    t = min(t, router.centroids.shape[0])
    return ops.centroid_assign(
        q, q2, router.centroids, router.c2, t=t, backend=backend
    )


def route_entries(
    router: Router,
    queries: jax.Array,
    beam: int,
    *,
    t: int = 4,
    backend: str = "auto",
) -> jax.Array:
    """Per-query beam seeds: the member rows of the query's top-t
    centroids, nearest-member-major (slot-major interleave so every
    probed centroid contributes its closest members first). (nq, beam)
    i32, -1 = hole (caller falls back to a random draw per hole)."""
    _, top = top_centroids(router, queries, t, backend=backend)  # (nq, t)
    mem = router.members.idx[top]                                # (nq, t, m)
    ent = jnp.moveaxis(mem, 1, 2).reshape(queries.shape[0], -1)  # (nq, m*t)
    if ent.shape[1] >= beam:
        ent = ent[:, :beam]
    else:
        ent = jnp.pad(
            ent, ((0, 0), (0, beam - ent.shape[1])), constant_values=-1
        )
    return ent.astype(jnp.int32)


def router_insert(
    router: Router, ids: jax.Array, q: jax.Array, *, backend: str = "auto"
) -> Router:
    """Incremental insert maintenance: assign each new row to its nearest
    centroid, bump counts, and merge the rows into that centroid's member
    list (grouped via compact_pairs — several inserts may share a
    centroid, so the dense merge is used; c is small)."""
    q = q.astype(jnp.float32)
    q2 = jnp.sum(q * q, axis=1)
    d, ci = ops.centroid_assign(
        q, q2, router.centroids, router.c2, t=1, backend=backend
    )
    ci0, d0 = ci[:, 0], d[:, 0]
    assign = router.assign.at[ids].set(ci0, mode="drop")
    counts = router.counts.at[ci0].add(1, mode="drop")
    c = router.centroids.shape[0]
    w = max(1, min(router.members.idx.shape[1], int(ids.shape[0])))
    cd, cid = compact_pairs(ci0, ids.astype(jnp.int32), d0, c, w)
    members, _ = heap.merge(router.members, cd, cid, False, backend=backend)
    return router._replace(
        assign=assign, counts=counts, members=members,
        stale=router.stale + jnp.int32(ids.shape[0]),
    )


def router_delete(
    router: Router, ids: jax.Array, alive: jax.Array, *,
    backend: str = "auto",
) -> Router:
    """Incremental delete maintenance: release assignments, decrement
    counts, purge dead rows from the member lists."""
    old = router.assign[ids]
    valid = old >= 0
    counts = router.counts.at[jnp.where(valid, old, 0)].add(
        -valid.astype(jnp.int32), mode="drop"
    )
    assign = router.assign.at[ids].set(-1, mode="drop")
    members, _ = heap.purge(router.members, alive, backend=backend)
    return router._replace(
        assign=assign, counts=counts, members=members,
        stale=router.stale + jnp.int32(ids.shape[0]),
    )


def needs_rebuild(router: Router, live: int, cfg: RouterConfig) -> bool:
    """Lazy rebuild policy: accumulated insert/delete drift past
    ``rebuild_frac`` of the live count means the centroids no longer
    describe the data — rebuild on the next mutation."""
    return int(router.stale) > cfg.rebuild_frac * max(int(live), 1)
