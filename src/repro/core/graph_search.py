"""Query-time search over a built K-NN graph — fused, batched, blocked.

This is the serving-side consumer of the paper's artifact: given the
NN-Descent graph, answer nearest-neighbor queries by beam search restricted
to the K-NN graph (NSW/NSG-style, fixed shapes). Used by serve/knn_lm.py,
the online store's query path, and the online insert's seeding.

Two implementations behind ``SearchConfig.backend``:

  * **fused** (auto | pallas | interpret) — the serving counterpart of the
    fused build join: queries are processed in blocks of
    ``SearchConfig.q_block``; each round expands the top-``expand``
    unexpanded pool nodes of EVERY query in the block at once (the
    friend-of-a-friend principle — Baron & Darling — is what lets several
    frontier nodes expand per round without losing convergence, and Wang
    et al.'s GPU construction shows the win of batching traversal into
    wide fixed-shape rounds). The E·k gathered neighbor rows become one
    (q_block, E·k) distance tile on the MXU (kernels/knn_search.py, norms
    hoisted once per batch), the ``knn_join_select`` partial top-C
    machinery reduces the tile under the pool's k-th-distance prefilter
    (no per-round full argsorts) to at most min(beam, E·k) sorted
    candidates, and ``ops.knn_pool_insert`` inserts those into the sorted
    pool (dedup by id), one candidate per step over the pool's lanes: the
    round's merge costs O(beam·E·k), not a beam-long extraction over pool
    plus candidates. It carries the NeighborLists ``new`` flag, reused as
    "not yet expanded". Sequential depth drops from ``rounds``
    to ~``rounds/expand``, with a convergence early-out when no query in
    the block has an unexpanded pool entry left.

  * **ref** — the original one-node-per-round greedy loop, retained as
    the parity oracle (same interface, per-query vmap, full argsorts).

``rounds`` is the *expansion budget* (total pool nodes expanded per
query) under both backends: the fused path runs ceil(rounds/expand)
rounds of ``expand`` expansions, so with expand | rounds (the default
and every shipped config) both backends expand exactly ``rounds`` nodes;
otherwise the fused budget rounds UP to the next multiple of ``expand``
(core/online.py's analytic eval bound accounts for this).

Entry points: when ``entry`` is None and a ``router`` is passed (the
serving default — MutableKNNStore / KNNDatastore thread theirs), each
query's beam is seeded from the member rows of its top-``router_t``
centroids (core/router.py — the hierarchical entry points that fixed the
large-n recall collapse); holes, and the no-router / ``router="off"`` /
``backend="ref"`` cases, fall back to a keyed draw uniform over live
(and filter-admitted) rows. When ``key`` is None it is derived from the
*content* of the query batch instead of a silent constant, so repeated
serving batches stop reusing identical entry points while identical
batches stay deterministic; serving callers should still thread an
explicit key (serve/knn_lm.knn_logits, core/online.knn_insert do).

**Metric** (``SearchConfig.metric``: l2 | cosine | mips): the kernels
only ever compute squared l2 — cosine and MIPS ride the input-side
reductions of core/metric.py. The CORPUS handed to ``graph_search`` must
already be transformed (stores built with ``OnlineConfig.metric`` /
``DescentConfig.metric`` do this once at build/insert); the QUERIES are
transformed here, once per batch (cosine: row-normalize; mips: append
the zero coordinate — realized as zero right-padding, which is also what
feature padding does, so any narrower query widens safely). Returned
distances are transformed-space squared l2 (monotone in the native
metric; ``metric.similarity_from_dist`` converts back exactly).

**Filtered search** (``filter_ids``): a caller-supplied predicate mask —
(n,) shared across the batch, or (q, n) per query (True = row admitted).
It rides the exact alive-mask path the tombstones use: filtered rows are
neither expanded nor returned (their ids fold to -1 before the distance
tile, and ``kernels/knn_search.py``'s epilogue maps id -1 to +inf), so a
filtered-out id can never surface — the zero-leakage contract the CI
metric lane gates. Highly selective filters cost recall the way mass
deletions do: the beam must traverse THROUGH admitted rows only (see
docs/METRICS.md; ``metric.filter_frac`` reports the admitted fraction).
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import heap, quantize
from repro.core import metric as metric_mod
from repro.core.heap import NeighborLists
from repro.core.quantize import QuantizedStore
from repro.core.spans import span
from repro.kernels import ops


_BIG = 3.0e38


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    beam: int = 32          # pool width per query
    rounds: int = 24        # expansion budget: pool nodes expanded/query
    expand: int = 4         # E: nodes expanded per round (fused path)
    q_block: int = 256      # queries per fused block (compile-once shape)
    backend: str = "auto"   # auto | pallas | interpret = fused kernels;
                            # ref = the greedy one-node-per-round oracle
    select_c: int = 0       # candidate width handed to the pool merge
                            # (0 = the most the tile holds, min(beam,
                            # E*k); the top-C select reduces the E*k tile
                            # to this before the pool insert)
    precision: str = "f32"  # f32 | bf16 | int8 — candidate-SCORING dtype
                            # (kernels/l2_quant.py). Quantized modes run
                            # the whole beam traversal on the quantized
                            # store and re-rank the final pool with the
                            # exact fp32 kernel before returning, so the
                            # output distances stay exact; quantization
                            # costs bounded candidate-recall noise only.
                            # backend="ref" (the parity oracle) is always
                            # fp32 and ignores this knob.
    metric: str = "l2"      # l2 | cosine | mips — metric the distances
                            # realize via core/metric.py's input-side
                            # reductions (the kernels stay pure squared
                            # l2). The corpus must be pre-transformed
                            # (stores with a matching OnlineConfig.metric
                            # are); queries transform per batch inside
                            # graph_search. Returned distances are
                            # transformed-space l2 — monotone in the
                            # native metric, convertible back exactly
                            # via metric.similarity_from_dist.
    router: str = "auto"    # auto = seed the beam from the router's
                            # centroid member lists when a router is
                            # passed; off = always random entries.
                            # backend="ref" keeps random entries either
                            # way (the parity oracle predates the router).
    router_t: int = 4       # centroids probed per query when routing
    strict: bool = False    # admission policy for poisoned query batches
                            # (NaN/Inf rows): True rejects the whole
                            # batch with ValueError; False (default)
                            # sanitizes — bad rows are zeroed for the
                            # traversal, their outputs overwritten with
                            # (+inf, -1), and a RuntimeWarning reports
                            # the count. Dim mismatches always reject
                            # (there is no safe way to guess features).
    max_rounds_deadline: float = 0.0
                            # per-q_block time slice in seconds; 0 = off.
                            # Once the batch has spent its cumulative
                            # slice, remaining blocks run with the
                            # expansion budget cut to one fused round
                            # (rounds=expand) — degraded recall, never a
                            # stall. Ignored under tracing (shard_map).
    fixed_block: bool = False
                            # True pads EVERY batch to the full q_block
                            # (one compiled shape, full-block latency for
                            # any batch size — the legacy serving quantum,
                            # kept as the SLO baseline). False (default)
                            # runs each batch at its q_block_bucket ladder
                            # step: powers of two up to q_block, compiled
                            # once per bucket, so a 7-query burst pays the
                            # 8-block, not the q_block-block.

    @property
    def n_rounds(self) -> int:
        """Fused sequential depth: ceil(rounds / expand)."""
        return max(1, -(-self.rounds // self.expand))


def q_block_bucket(nq: int, cfg: "SearchConfig") -> int:
    """The bucketed ``q_block`` ladder: the query-block shape a batch of
    ``nq`` queries runs at. Buckets are powers of two capped at
    ``cfg.q_block`` (step-quantized exactly like the online store's
    capacity doubling), so the set of compiled block shapes stays
    O(log q_block) — each bucket compiles once and is reused by every
    batch that lands in it — while a small interactive burst stops
    paying the full-block distance tile (pad waste stays < 2x).
    ``cfg.fixed_block`` pins the ladder to the single legacy full-block
    quantum (the measured baseline in benchmarks/bench_slo.py).
    Metric- and filter-independent: the bucket depends only on ``nq``
    (query transforms are per-row, and per-query ``filter_ids`` masks
    are sliced along the query axis with the block)."""
    if cfg.fixed_block or nq <= 0:
        return max(1, cfg.q_block)
    return max(1, min(cfg.q_block, 1 << (nq - 1).bit_length()))


class SearchStats(NamedTuple):
    """Totals of one fused search over its query blocks
    (``graph_search(..., stats=True)``): what the rounds' pool inserts
    were offered and what they kept."""
    blocks: int     # query blocks run
    rounds: int     # rounds run, summed over the blocks
    offered: int    # candidates that reached the insert past the dedup
    inserted: int   # candidates in the pool after their round's insert


@functools.partial(jax.jit, static_argnames=("hops", "capacity"))
def expand_frontier(
    graph_idx: jax.Array,   # (n, k) neighbor ids, -1 = empty
    seeds: jax.Array,       # (s,) seed row ids, -1 = padding
    *,
    hops: int = 1,
    capacity: int,
    alive: jax.Array | None = None,   # (n,) bool — rows to keep
):
    """h-hop outbound closure of ``seeds`` over the K-NN graph, compacted
    into a padded id buffer (the localized-update frontier of
    core/online.py: after a change at ``seeds``, refinement only needs to
    propagate along this closure — the friend-of-a-friend principle).

    Returns (ids (capacity,) int32 ascending with -1 padding at the tail,
    mask (n,) bool). When the closure exceeds ``capacity`` the rows
    NEAREST the seeds (fewest hops, ids breaking ties) are kept — the
    old smallest-id truncation systematically dropped late rows on
    hub-heavy closures (ROADMAP watch item). The mask is exact either
    way. The hop passes are O(n*k) bitwise work — no distance
    evaluations; the point is that the *expensive* per-row kernels then
    run on the compacted ids. Pure graph topology, so metric-oblivious
    (it never sees features); ``alive`` folds out tombstoned rows —
    query-time filter masks do NOT apply here (the frontier is an
    update-path construct, not a query result).
    """
    n, _ = graph_idx.shape
    # scatter-min BFS: hop[i] = fewest hops from any seed (hops+1 = unseen)
    hop = jnp.full((n,), hops + 1, jnp.int32)
    hop = hop.at[jnp.where(seeds >= 0, seeds, n)].min(0, mode="drop")
    for h in range(1, hops + 1):
        hit = (hop[:, None] < h) & (graph_idx >= 0)
        tgt = jnp.where(hit, graph_idx, n).reshape(-1)
        hop = hop.at[tgt].min(h, mode="drop")
    mask = hop <= hops
    if alive is not None:
        mask &= alive
    big = jnp.iinfo(jnp.int32).max
    kcap = min(capacity, n)
    # lexicographic (hop, id) packed into one key — (hops+2)*n stays well
    # inside int32 for every supported store size
    score = jnp.where(mask, hop * n + jnp.arange(n, dtype=jnp.int32), big)
    sel = jnp.sort(score)[:kcap]
    # recover ids and re-sort ascending (the -1 tail sorts last via the
    # n sentinel) — _frontier_slots searchsorts over this buffer
    ids = jnp.sort(jnp.where(sel < big, sel % n, n))
    ids = jnp.where(ids < n, ids, -1).astype(jnp.int32)
    if kcap < capacity:
        ids = jnp.concatenate(
            [ids, jnp.full((capacity - kcap,), -1, jnp.int32)]
        )
    return ids, mask


# ---------------------------------------------------------------------------
# entry-point seeding
# ---------------------------------------------------------------------------


def _batch_key(queries: jax.Array) -> jax.Array:
    """Content-derived entry key: replaces the retired silent
    ``jax.random.key(0)`` fallback. Two folds: the plain feature sum plus
    a position-weighted sum (bounded cos weights, so the positional term
    survives f32 accumulation at any batch size) — permuted batches share
    the first hash but not the second, so shuffled copies of a batch no
    longer reuse identical entry points."""
    flat = queries.astype(jnp.float32).reshape(-1)
    w = jnp.cos(jnp.arange(flat.shape[0], dtype=jnp.float32) * 1.6180339)
    h1 = jax.lax.bitcast_convert_type(jnp.sum(flat), jnp.uint32)
    h2 = jax.lax.bitcast_convert_type(jnp.sum(flat * w), jnp.uint32)
    return jax.random.fold_in(jax.random.fold_in(jax.random.key(0), h1), h2)


def _draw_entries(
    key: jax.Array, n: int, beam: int, alive: jax.Array | None
) -> jax.Array:
    """One entry per beam slot, uniform over live rows. Both branches use
    the keyed top-k draw — sampling WITHOUT replacement (the retired
    ``randint`` draw produced duplicate ids that the pool merge then
    dedup'd away, silently wasting beam slots). Width is min(beam, n)."""
    w = jax.random.uniform(key, (n,))
    if alive is not None:
        w = jnp.where(alive, w, -1.0)
    _, entry = jax.lax.top_k(w, min(beam, n))
    return entry.astype(jnp.int32)


# ---------------------------------------------------------------------------
# public dispatcher
# ---------------------------------------------------------------------------


def _admit_queries(queries: jax.Array, d: int, strict: bool):
    """Admission check at the search boundary: a poisoned batch (NaN/Inf
    rows, wrong feature dim) must not propagate through the pool merge —
    one non-finite distance poisons every merge it touches.

    Returns (queries, bad_rows) where bad_rows is a (q,) bool mask of
    sanitized rows (None when the batch is clean). ``strict`` rejects
    non-finite rows with ValueError instead of sanitizing; a feature-dim
    mismatch always rejects (no safe way to guess features). Skipped
    entirely under tracing — graph_search runs inside shard_map bodies,
    where the DRIVER (graph_search_sharded) has already admitted the
    concrete batch."""
    if isinstance(queries, jax.core.Tracer) or queries.shape[0] == 0:
        return queries, None
    if queries.ndim != 2 or queries.shape[1] != d:
        raise ValueError(
            f"query batch has shape {tuple(queries.shape)}; corpus rows "
            f"have feature dim {d} — rejecting the batch at admission"
        )
    finite = jnp.all(jnp.isfinite(queries), axis=1)
    if bool(jnp.all(finite)):
        return queries, None
    n_bad = int(jnp.sum(~finite))
    if strict:
        raise ValueError(
            f"query batch contains {n_bad} non-finite row(s) (NaN/Inf) — "
            "rejected (SearchConfig.strict=True)"
        )
    warnings.warn(
        f"sanitized {n_bad} non-finite query row(s); their results are "
        "empty (+inf/-1)", RuntimeWarning, stacklevel=3)
    return jnp.where(finite[:, None], queries, 0.0), ~finite


def _mask_bad_rows(dist, idx, bad_rows):
    """Overwrite sanitized rows' outputs with the empty-slot sentinel."""
    if bad_rows is None:
        return dist, idx
    return (jnp.where(bad_rows[:, None], jnp.inf, dist),
            jnp.where(bad_rows[:, None], -1, idx))


def graph_search(
    x: jax.Array,          # (n, d) corpus (feature-padded ok)
    graph_idx: jax.Array,  # (n, k) neighbor ids
    queries: jax.Array,    # (q, d)
    *,
    k_out: int = 10,
    beam: int = 32,
    rounds: int = 24,
    entry: jax.Array | None = None,   # (e,) entry point ids
    key: jax.Array | None = None,
    alive: jax.Array | None = None,   # (n,) bool — tombstone mask
    x2: jax.Array | None = None,      # (n,) cached squared norms
    cfg: SearchConfig | None = None,
    qstore: QuantizedStore | None = None,   # cached quantized corpus
    router=None,                            # core/router.Router — routed seeds
    filter_ids: jax.Array | None = None,    # (n,) shared or (q, n) per-query
                                            # predicate mask (True = admitted)
    stats: bool = False,
):
    """Returns (dist (q, k_out), idx (q, k_out)) ascending; empty slots
    are (+inf/_BIG, -1). With ``stats`` the fused path also returns a
    ``SearchStats`` of its rounds, read back to the host.

    ``cfg`` wins over the legacy ``beam``/``rounds`` kwargs when given.
    ``entry`` may be (e,) shared across the batch or (q, e) per-query
    (-1 = hole). With ``router`` given (and ``cfg.router != "off"``) the
    beam is seeded per-query from the member rows of the query's top
    ``cfg.router_t`` centroids — the hierarchical entry points that fix
    the large-n recall collapse of uniform-random seeding; holes (dead or
    missing members) fall back to the random draw. ``backend="ref"``
    keeps random entries (the parity oracle).
    With ``alive`` given (the online store's tombstone mask), dead nodes
    are neither expanded nor returned: entry points are drawn from live
    rows only and dead neighbors are masked out of the candidate tile.
    ``x2`` lets callers with a cached norm vector (MutableKNNStore) skip
    the per-call recomputation; queries' norms are hoisted once per batch
    either way.

    ``cfg.metric`` selects l2 / cosine / mips via the input-side
    reductions (module docstring): the corpus/``x2`` must already be
    transformed, the queries are transformed here, distances come back
    as transformed-space squared l2 under EVERY backend (``"ref"``
    included — the oracle is metric-general through the same reduction).

    ``filter_ids`` restricts results to admitted rows — (n,) bool shared
    across the batch, or (q, n) bool per query. Filtered rows behave
    exactly like tombstoned ones for this call: never seeded, never
    expanded, never returned (zero leakage, gated in CI). Both layouts
    work under every backend and precision.

    With ``cfg.precision`` "int8"/"bf16" the traversal scores candidates
    on the quantized corpus mirror and re-ranks the final pool fp32 (see
    SearchConfig). ``qstore`` passes a cached mirror (MutableKNNStore /
    KNNDatastore keep one); without it the mirror is quantized here, once
    per call — fine for one-shot searches, wasteful for serving loops.
    """
    if cfg is None:
        cfg = SearchConfig(beam=beam, rounds=rounds)
    if stats and cfg.backend == "ref":
        raise ValueError("stats count the fused path's rounds; "
                         "backend='ref' runs none")
    no_stats = SearchStats(0, 0, 0, 0)
    x = x.astype(jnp.float32)
    queries = queries.astype(jnp.float32)
    if cfg.metric == "cosine":
        queries = metric_mod.normalize_rows(queries)
    elif cfg.metric == "mips" and queries.ndim == 2 \
            and queries.shape[1] < x.shape[1]:
        # the mips query transform is literally zero right-padding (the
        # augmented coordinate is 0), same as feature padding — widen
        # narrower query batches up to the transformed corpus width
        queries = jnp.pad(queries, ((0, 0), (0, x.shape[1]
                                             - queries.shape[1])))
    else:
        metric_mod.check_metric(cfg.metric)
    with span("search.admit", rows=queries.shape[0]):
        queries, bad_rows = _admit_queries(queries, x.shape[1], cfg.strict)
    n = graph_idx.shape[0]
    filt = None
    if filter_ids is not None:
        filter_ids = jnp.asarray(filter_ids, bool)
        if filter_ids.shape[-1] != n:
            raise ValueError(
                f"filter_ids covers {filter_ids.shape[-1]} rows; the "
                f"graph has {n}")
        if filter_ids.ndim == 1:
            # a shared predicate IS a tombstone mask for this call —
            # fold it into `alive` and the whole existing path (entry
            # draw, candidate masking, epilogue) enforces it for free
            alive = filter_ids if alive is None else alive & filter_ids
        else:
            filt = filter_ids
    if n == 0:
        # empty corpus (a store before its first insert): every query
        # gets the empty result, same contract as a fully-dead store
        out = (jnp.full((queries.shape[0], k_out), jnp.inf, jnp.float32),
               jnp.full((queries.shape[0], k_out), -1, jnp.int32))
        return out + (no_stats,) if stats else out
    if x2 is None:
        x2 = jnp.sum(x * x, axis=1)
    if entry is None:
        routed = (router is not None and cfg.router != "off"
                  and cfg.backend != "ref" and queries.shape[0] > 0)
        width = min(cfg.beam, n)
        if routed:
            # probe the FULL member set of the top-t centroids (IVF-style:
            # up to t*m candidates), not just beam of them — the seed tile
            # scores every candidate against the query and the bounded
            # merge keeps the best ``beam``, so wider probing costs one
            # wider seed tile, never a wider traversal
            t = min(cfg.router_t, router.centroids.shape[0])
            width = min(max(cfg.beam, t * router.members.idx.shape[1]), n)
        with span("search.route", width=width):
            key = _batch_key(queries) if key is None else key
            if routed:
                from repro.core.router import route_entries
                ent = route_entries(
                    router, queries, width, t=cfg.router_t,
                    backend=cfg.backend,
                )                                       # (q, e), -1 holes
                if alive is not None:
                    ent = jnp.where(
                        (ent >= 0) & alive[jnp.clip(ent, 0, n - 1)], ent, -1
                    )
                # holes (dead or missing members) fall back to a random draw
                rnd = _draw_entries(key, n, width, alive)
                entry = jnp.where(ent >= 0, ent, rnd[None, :])
            else:
                entry = _draw_entries(key, n, cfg.beam, alive)
    entry = entry.astype(jnp.int32)
    if filt is not None:
        # per-query predicates need per-query entries: broadcast shared
        # seeds, drop seeds the query's own filter rejects, and refill
        # the holes from a keyed draw over each query's admitted live
        # rows (same sampling-without-replacement trick as
        # _draw_entries, one weight vector shared across the batch)
        if entry.ndim == 1:
            entry = jnp.broadcast_to(
                entry[None, :], (queries.shape[0], entry.shape[0]))
        fok = jnp.take_along_axis(filt, jnp.clip(entry, 0, n - 1), axis=1)
        entry = jnp.where((entry >= 0) & fok, entry, -1)
        key = _batch_key(queries) if key is None else key
        w = jax.random.uniform(jax.random.fold_in(key, 7), (n,))
        if alive is not None:
            w = jnp.where(alive, w, -1.0)
        fd, fent = jax.lax.top_k(
            jnp.where(filt, w[None, :], -1.0), min(entry.shape[1], n))
        fent = jnp.where(fd >= 0.0, fent, -1).astype(jnp.int32)
        if fent.shape[1] < entry.shape[1]:
            fent = jnp.pad(
                fent, ((0, 0), (0, entry.shape[1] - fent.shape[1])),
                constant_values=-1)
        entry = jnp.where(entry >= 0, entry, fent)
    if cfg.precision == "f32" or cfg.backend == "ref":
        qstore = None
    elif qstore is None or qstore.mode != cfg.precision:
        # a cached mirror of the WRONG mode (e.g. an int8 store searched
        # with precision="bf16") would be scored as raw codes by the
        # other kernel — silently garbage. Quantize fresh instead.
        qstore = quantize.quantize_corpus(x, cfg.precision)

    if cfg.backend == "ref":
        rd, ri = _graph_search_ref(
            x, x2, graph_idx, queries, entry, alive, filt,
            k_out=k_out, beam=cfg.beam, rounds=cfg.rounds,
        )
        return _mask_bad_rows(rd, ri, bad_rows)

    # fused batched path: pad the batch to whole q_blocks, run the jitted
    # block search per block, slice the pad off. Small batches (decode
    # steps, insert seeding, interactive bursts) run at their
    # q_block_bucket ladder step — next power of two, compiled once per
    # bucket — unless cfg.fixed_block pins the full-block quantum.
    nq = queries.shape[0]
    if nq == 0:     # idle serving tick / empty insert batch
        out = (jnp.zeros((0, k_out), jnp.float32),
               jnp.full((0, k_out), -1, jnp.int32))
        return out + (no_stats,) if stats else out
    qb = q_block_bucket(nq, cfg)
    pad = (-nq) % qb
    with span("search.dispatch", blocks=(nq + pad) // qb, q_block=qb):
        qp = jnp.pad(queries, ((0, pad), (0, 0)))
        q2 = jnp.sum(qp * qp, axis=1)
        if entry.ndim == 2:     # per-query seeds ride along with their block
            entry = jnp.pad(entry, ((0, pad), (0, 0)), constant_values=-1)
        if filt is not None:    # pad queries admit everything (sliced off)
            filt = jnp.pad(filt, ((0, pad), (0, 0)), constant_values=True)
        # Deadline degradation: once the batch has spent its cumulative
        # per-block slice, remaining blocks run with the expansion budget cut
        # to ONE fused round — the answer degrades (fewer expansions, lower
        # recall), the latency does not. Needs wall time, so each block is
        # synced when armed; meaningless under tracing (no wall clock), so
        # the knob is ignored there.
        deadline = cfg.max_rounds_deadline
        use_deadline = deadline > 0.0 and not isinstance(queries,
                                                        jax.core.Tracer)
        # host-side knobs (the deadline value, the fixed_block baseline flag)
        # must not fragment _search_block's static-cfg compile cache: the
        # scheduler propagates a FRESH max_rounds_deadline per dispatch, and
        # keying compiles on it would recompile every batch for an identical
        # traced computation
        run_cfg = dataclasses.replace(cfg, max_rounds_deadline=0.0,
                                      fixed_block=False)
        cut_cfg = None
        t0 = time.monotonic() if use_deadline else 0.0
        outs_d, outs_i, counts = [], [], []
        for bi, s in enumerate(range(0, nq + pad, qb)):
            bcfg = run_cfg
            if use_deadline and bi > 0 \
                    and time.monotonic() - t0 > deadline * bi:
                if cut_cfg is None:     # one extra (cached) compile, ever
                    cut_cfg = dataclasses.replace(run_cfg, rounds=cfg.expand)
                bcfg = cut_cfg
            ent_b = entry if entry.ndim == 1 else entry[s:s + qb]
            od, oi, *cnt = _search_block(
                x, x2, graph_idx, qp[s:s + qb], q2[s:s + qb], ent_b, alive,
                None if filt is None else filt[s:s + qb],
                qstore, k_out=k_out, cfg=bcfg, stats=stats,
            )
            counts += cnt
            if use_deadline:
                od.block_until_ready()
            outs_d.append(od)
            outs_i.append(oi)
        out_d = outs_d[0] if len(outs_d) == 1 else jnp.concatenate(outs_d)
        out_i = outs_i[0] if len(outs_i) == 1 else jnp.concatenate(outs_i)
        out = _mask_bad_rows(out_d[:nq], out_i[:nq], bad_rows)
    if not stats:
        return out
    total = [int(t) for t in sum(counts)]
    return out + (SearchStats(len(counts), *total),)


# ---------------------------------------------------------------------------
# fused batched multi-expansion search
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k_out", "cfg", "stats"))
def _search_block(
    x: jax.Array,          # (n, dp) f32 corpus
    x2: jax.Array,         # (n,) corpus squared norms (hoisted)
    graph_idx: jax.Array,  # (n, k)
    q: jax.Array,          # (qb, dp) f32 query block
    q2: jax.Array,         # (qb,) query squared norms (hoisted)
    entry: jax.Array,      # (e,) shared or (qb, e) per-query entry ids
    alive: jax.Array | None,
    filt: jax.Array | None,          # (qb, n) per-query predicate mask
    qstore: QuantizedStore | None,   # quantized corpus mirror (quant only)
    *,
    k_out: int,
    cfg: SearchConfig,
    stats: bool = False,
):
    """One query block of the fused search (see module docstring).
    ``filt`` (per-query filtered search) masks candidates exactly like
    ``alive`` does, gathered per query row. Returns (dist, idx), and with
    ``stats`` also the block's (3,) int32 totals: rounds run, candidates
    offered to the pool insert past its dedup, candidates it kept."""
    n, k = graph_idx.shape
    qb = q.shape[0]
    beam = cfg.beam
    e = cfg.expand
    # a select wider than the E*k tile only pads with (+inf, -1)
    c_sel = cfg.select_c or min(beam, e * k)
    rows = jnp.arange(qb, dtype=jnp.int32)[:, None]

    # quantized scoring stage: the query block is quantized ONCE per block
    # (the serving twin of the hoisted norms) at the MIRROR's width — the
    # mirror drops the fp32 layout's zero feature padding (quantize.
    # mirror_width) — and the whole traversal (seeds, candidate tiles,
    # pool-kth prefilter) runs on quantized distances so comparisons stay
    # self-consistent; the exact fp32 re-rank of the final pool happens
    # after the round loop
    quant = cfg.precision != "f32" and qstore is not None
    # the phases run under named scopes (spans.SCOPES): the chip trace
    # carries each in its ops' ``tf_op``, and the benchmark adds up
    # device time per phase from it
    with jax.named_scope("seed"):
        if quant:
            qq = quantize.quantize_corpus(q, cfg.precision,
                                          width=qstore.data.shape[1])
        # ---- seed the pool: all entry distances in ONE blocked matmul, then
        # one bounded merge (dedups repeated entries, drops dead ones)
        ent = jnp.clip(entry, 0, n - 1)
        if entry.ndim == 2:
            # per-query (routed) seeds: the gathered (qb, e, dp) rows go
            # through the same masked search tile as candidate scoring, so
            # -1 holes come back +inf and vanish in the merge
            eids = entry
            if alive is not None:
                eids = jnp.where(alive[ent], eids, -1)
            if filt is not None:   # filt implies per-query entries (dispatcher)
                eids = jnp.where(jnp.take_along_axis(filt, ent, 1), eids, -1)
            if quant:
                c2q = jnp.where(eids >= 0, qstore.x2[ent], 0.0)
                if cfg.precision == "int8":
                    ed = ops.knn_search_dists_q8(
                        qq.data, qq.scale, qq.x2, qstore.data[ent],
                        qstore.scale[ent], c2q, eids, backend=cfg.backend,
                    )                                       # (qb, E0)
                else:
                    ed = ops.knn_search_dists_bf16(
                        qq.data, qq.x2, qstore.data[ent], c2q, eids,
                        backend=cfg.backend,
                    )                                       # (qb, E0)
            else:
                ed = ops.knn_search_dists(
                    q, q2, x[ent], jnp.where(eids >= 0, x2[ent], 0.0), eids,
                    backend=cfg.backend,
                )                                           # (qb, E0)
        elif quant:
            ab = qq.data.astype(jnp.float32) @ (
                qstore.data[ent].astype(jnp.float32).T
            )
            ab = (qq.scale[:, None] * qstore.scale[ent][None, :]) * ab
            ed = jnp.maximum(
                qq.x2[:, None] + qstore.x2[ent][None, :] - 2.0 * ab, 0.0
            )                                               # (qb, E0)
            eids = jnp.broadcast_to(entry[None, :], ed.shape)
            if alive is not None:
                eids = jnp.where(alive[ent][None, :], eids, -1)
        else:
            ed = jnp.maximum(
                q2[:, None] + x2[ent][None, :] - 2.0 * q @ x[ent].T, 0.0
            )                                               # (qb, E0)
            eids = jnp.broadcast_to(entry[None, :], ed.shape)
            if alive is not None:
                eids = jnp.where(alive[ent][None, :], eids, -1)
        pool = NeighborLists(
            jnp.full((qb, beam), jnp.inf, jnp.float32),
            jnp.full((qb, beam), -1, jnp.int32),
            jnp.zeros((qb, beam), bool),        # ``new`` == not yet expanded
        )
        pool, _ = heap.merge_kernel(
            pool, jnp.where(eids >= 0, ed, jnp.inf), eids, backend=cfg.backend
        )

    inf_q = jnp.full((qb,), jnp.inf, jnp.float32)
    slot_iota = jnp.broadcast_to(
        jnp.arange(beam, dtype=jnp.int32)[None, :], (qb, beam)
    )

    def round_fn(state):
        pool_d, pool_i, pool_new, r = state[:4]
        with jax.named_scope("select"):
            # top-E unexpanded pool slots per query (partial top-C select
            # — the same machinery as the build join, no full argsort)
            _, ss = ops.knn_join_select(
                pool_d, jnp.where(pool_new & (pool_i >= 0), slot_iota, -1),
                inf_q, c=e, backend=cfg.backend,
            )                                           # (qb, E) slots
            can = ss >= 0
            safe_s = jnp.where(can, ss, 0)
            nodes = jnp.where(can, jnp.take_along_axis(pool_i, safe_s, 1),
                              -1)
            # mark expanded (disabled writes go out of bounds -> dropped)
            pool_new = pool_new.at[rows, jnp.where(can, ss, beam)].set(
                False, mode="drop"
            )
        with jax.named_scope("gather"):
            # adjacency + feature gather for the whole block, then the
            # fused distance tile with validity/alive masking in the
            # epilogue
            nbrs = graph_idx[jnp.clip(nodes, 0, n - 1)]     # (qb, E, k)
            ok = can[:, :, None] & (nbrs >= 0)
            if alive is not None:
                ok &= alive[jnp.clip(nbrs, 0, n - 1)]
            if filt is not None:
                # per-query predicate: filtered candidates fold to -1
                # here, the epilogue maps id -1 to +inf — zero-leakage by
                # the same mechanism tombstones use
                ok &= jnp.take_along_axis(
                    filt, jnp.clip(nbrs, 0, n - 1).reshape(qb, e * k), 1
                ).reshape(qb, e, k)
            cand = jnp.where(ok, nbrs, -1).reshape(qb, e * k)
            safe_c = jnp.where(cand >= 0, cand, 0)
            if quant:
                # quantized scoring tile: int8/bf16 gathered rows (2-4x
                # fewer HBM bytes), scales + norm expansion fused in the
                # epilogue
                rows_c = qstore.data[safe_c]
                scale_c = qstore.scale[safe_c] \
                    if cfg.precision == "int8" else None
                c2 = jnp.where(cand >= 0, qstore.x2[safe_c], 0.0)
            else:
                rows_c = x[safe_c]
                c2 = jnp.where(cand >= 0, x2[safe_c], 0.0)
        with jax.named_scope("score"):
            if not quant:
                dd = ops.knn_search_dists(
                    q, q2, rows_c, c2, cand, backend=cfg.backend,
                )                                       # (qb, E*k)
            elif cfg.precision == "int8":
                dd = ops.knn_search_dists_q8(
                    qq.data, qq.scale, qq.x2, rows_c, scale_c, c2, cand,
                    backend=cfg.backend,
                )                                       # (qb, E*k)
            else:
                dd = ops.knn_search_dists_bf16(
                    qq.data, qq.x2, rows_c, c2, cand, backend=cfg.backend,
                )                                       # (qb, E*k)
        with jax.named_scope("merge"):
            # pool-k-th prefilter + partial top-C, then the sorted
            # insert (dedup by id; inserted slots come back unexpanded)
            cd, ci = ops.knn_join_select(
                dd, cand, pool_d[:, -1], c=c_sel, backend=cfg.backend
            )
            pool_d, pool_i, pool_new, ins, off = ops.knn_pool_insert(
                pool_d, pool_i, pool_new, cd, ci, backend=cfg.backend
            )
        out = (pool_d, pool_i, pool_new, r + 1)
        if stats:
            out += (state[4] + jnp.sum(off), state[5] + jnp.sum(ins))
        return out

    def cond_fn(state):
        pool_d, pool_i, pool_new, r = state[:4]
        # early-out: every pool entry of every query already expanded
        with jax.named_scope("select"):
            return (r < cfg.n_rounds) & jnp.any(pool_new & (pool_i >= 0))

    # totals: the rounds run, and with stats the offered and inserted sums
    zero = jnp.zeros((), jnp.int32)
    pool_d, pool_i, _, *totals = jax.lax.while_loop(
        cond_fn, round_fn,
        (pool.dist, pool.idx, pool.new, zero) + (zero, zero) * stats,
    )
    # exact re-rank of the final pool: difference-form fp32 distances on
    # the gathered rows (metric.exact_sq_l2). For quantized scoring
    # this is stage two — quantization decided pool membership (bounded
    # recall noise), never the returned distances/order; for fp32 it
    # replaces the traversal's norm-expansion values
    with jax.named_scope("rerank"):
        dex = metric_mod.exact_sq_l2(q, x[jnp.clip(pool_i, 0, n - 1)],
                                     pool_i)
        out = ops.knn_join_select(
            dex, pool_i, jnp.full((qb,), jnp.inf, jnp.float32), c=k_out,
            backend=cfg.backend,
        )
    return out + (jnp.stack(totals),) if stats else out


# ---------------------------------------------------------------------------
# reference greedy loop (parity oracle)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k_out", "beam", "rounds"))
def _graph_search_ref(
    x: jax.Array,          # (n, dp) f32
    x2: jax.Array,         # (n,) corpus squared norms (hoisted)
    graph_idx: jax.Array,  # (n, k)
    queries: jax.Array,    # (q, dp) f32
    entry: jax.Array,      # (e,) shared or (q, e) per-query entry ids
    alive: jax.Array | None,
    filt: jax.Array | None,   # (q, n) per-query predicate mask
    *,
    k_out: int,
    beam: int,
    rounds: int,
):
    """The original one-node-per-round greedy search, kept as the fused
    path's parity oracle — metric-general through the same input-side
    reduction as the fused path (it sees transformed rows, computes pure
    l2), and filter-aware: ``filt`` rows mask entries and candidates
    exactly like ``alive`` does, vmapped per query. Norms are hoisted:
    x2 comes in precomputed and each query's norm is evaluated once per
    batch, not once per round."""
    n, k = graph_idx.shape
    if entry.ndim == 1:
        entry = jnp.broadcast_to(
            entry[None, :], (queries.shape[0], entry.shape[0])
        )

    def q_dist(q, q2s, ids):
        rows = x[ids]
        return jnp.maximum(x2[ids] - 2.0 * rows @ q + q2s, 0.0)

    def one_query(q, q2s, ent, frow):
        pool_i = jnp.full((beam,), -1, dtype=jnp.int32)
        pool_d = jnp.full((beam,), _BIG, dtype=jnp.float32)
        pool_e = jnp.zeros((beam,), dtype=bool)   # expanded?
        e = ent.shape[0]
        ve = ent >= 0
        pool_i = pool_i.at[:e].set(jnp.where(ve, ent, -1).astype(jnp.int32))
        pool_d = pool_d.at[:e].set(
            jnp.where(ve, q_dist(q, q2s, jnp.clip(ent, 0, n - 1)), _BIG)
        )
        if alive is not None:
            dead = (pool_i >= 0) & ~alive[jnp.clip(pool_i, 0, n - 1)]
            pool_d = jnp.where(dead, _BIG, pool_d)
        if frow is not None:
            shut = (pool_i >= 0) & ~frow[jnp.clip(pool_i, 0, n - 1)]
            pool_d = jnp.where(shut, _BIG, pool_d)

        def round_fn(_, state):
            pool_d, pool_i, pool_e = state
            # best unexpanded entry
            score = jnp.where(pool_e | (pool_i < 0), _BIG, pool_d)
            b = jnp.argmin(score)
            node = pool_i[b]
            can = score[b] < _BIG
            pool_e = pool_e.at[b].set(True)
            nbrs = graph_idx[jnp.clip(node, 0, n - 1)]       # (k,)
            nb_ok = (nbrs >= 0) & can
            if alive is not None:
                nb_ok &= alive[jnp.clip(nbrs, 0, n - 1)]
            if frow is not None:
                nb_ok &= frow[jnp.clip(nbrs, 0, n - 1)]
            nd = jnp.where(
                nb_ok, q_dist(q, q2s, jnp.clip(nbrs, 0, n - 1)), _BIG
            )
            # merge pool + neighbors, dedup by id, keep best `beam`
            all_i = jnp.concatenate([pool_i, jnp.where(nb_ok, nbrs, -1)])
            all_d = jnp.concatenate([pool_d, nd])
            all_e = jnp.concatenate([pool_e, jnp.zeros((k,), bool)])
            # dedup: mark later duplicates invalid (stable: pool first).
            # Sort-by-id adjacent-duplicate pass — O(m log m) instead of
            # the O(m^2) eq&earlier matrix; the stable sort keeps the
            # earliest (pool) occurrence first among equal ids, preserving
            # the expanded flag exactly like the matrix form did.
            sid = jnp.argsort(all_i, stable=True)
            si = all_i[sid]
            adj = jnp.concatenate(
                [jnp.zeros((1,), bool), si[1:] == si[:-1]]
            )
            dup = jnp.zeros_like(adj).at[sid].set(adj) & (all_i >= 0)
            all_d = jnp.where(dup | (all_i < 0), _BIG, all_d)
            order = jnp.argsort(all_d)[:beam]
            return all_d[order], all_i[order], all_e[order]

        pool_d, pool_i, pool_e = jax.lax.fori_loop(
            0, rounds, round_fn, (pool_d, pool_i, pool_e)
        )
        out_d, out_i = pool_d[:k_out], pool_i[:k_out]
        # dead / hole entry points survive in the pool at distance _BIG;
        # never surface them
        out_i = jnp.where(out_d >= _BIG, -1, out_i)
        # returned distances are exact: difference form, re-sorted
        dex = metric_mod.exact_sq_l2(q, x[jnp.clip(out_i, 0, n - 1)],
                                     out_i)
        out_d = jnp.where(out_i >= 0, dex, out_d)
        order = jnp.argsort(out_d, stable=True)
        return out_d[order], out_i[order]

    q2 = jnp.sum(queries * queries, axis=1)
    if filt is None:
        return jax.vmap(
            lambda q, q2s, ent: one_query(q, q2s, ent, None)
        )(queries, q2, entry)
    return jax.vmap(one_query)(queries, q2, entry, filt)
