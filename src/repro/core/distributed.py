"""Distributed K-NN-graph construction (the scale-out layer the paper's
single-core scope stops short of; DESIGN.md assumption change #4).

Points are sharded over the mesh's ``data`` axis under shard_map. Global
ids are ``shard * n_local + row``. Three collective patterns:

  * exact_knn_sharded — blocked brute force: the local block of features
    ring-rotates (collective_permute) P-1 times; each step every shard
    evaluates an (n_local x n_local) blocked-distance tile and folds the
    top-k into its running lists. Peak memory O(n_local * d); validates
    recall of the approximate build.
  * nn_descent_sharded_iteration — one NN-Descent iteration where
      - candidate features are fetched by the same feature ring (each
        shard absorbs the rows it sampled as the owning block passes), and
      - update routing is an all_to_all: each evaluated pair is bucketed
        by its receiver's owner shard and exchanged in fixed-size buckets.
  * reorder_sharded — the paper's greedy reorder run shard-locally on the
    locally-owned subgraph, followed by one all_gather of the per-shard
    permutations so every shard can rewrite its neighbor ids.
  * graph_search_sharded — the serving-side entry: replicated query
    blocks run the fused batched beam search on every shard's local
    subgraph, and one all_gather + top-k merges the per-shard results
    into global top-k (core/graph_search.py holds the per-shard search).

The per-shard inner work reuses the exact same selection/merge/blocked
kernels as the single-chip path. After the sampled iterations converge,
``build_knn_graph_sharded`` runs the same terminal polish rounds as the
single-chip build (``polish_sharded_round`` — exhaustive k*k
neighbor-of-neighbor join with the fused ``knn_join_select`` reduction,
neighbor lists and features fetched via the request-routed all_to_all).
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import faults, heap, selection
from repro.core import metric as metric_mod
from repro.core.graph_search import SearchConfig, graph_search
from repro.core.heap import NeighborLists
from repro.core.layout import chunked_rows, flatten_rows
from repro.core.nn_descent import DescentConfig, invert_candidates, pair_block
from repro.kernels import ops


def _ring_perm(axis: str, size: int):
    return [(i, (i + 1) % size) for i in range(size)]


def exact_knn_sharded(mesh: Mesh, x: jax.Array, k: int, *, axis: str = "data"):
    """Exact k-NN over points sharded along ``axis``. x: (n, d) global.

    Returns (dist (n, k), idx (n, k) global ids), sharded like x.
    """
    P_ = mesh.shape[axis]
    n, d = x.shape
    assert n % P_ == 0, (n, P_)
    n_local = n // P_

    def shard_fn(x_local):
        p = jax.lax.axis_index(axis)
        my_ids = p * n_local + jnp.arange(n_local, dtype=jnp.int32)
        x_local = x_local.astype(jnp.float32)
        x2_local = jnp.sum(x_local * x_local, axis=1)

        nl_d = jax.lax.pvary(jnp.full((n_local, k), jnp.inf, jnp.float32), (axis,))
        nl_i = jax.lax.pvary(jnp.full((n_local, k), -1, jnp.int32), (axis,))

        def step(s, carry):
            nl_d, nl_i, block, block2 = carry
            owner = (p - s) % P_
            ids = owner * n_local + jnp.arange(n_local, dtype=jnp.int32)
            dist = jnp.maximum(
                x2_local[:, None] + block2[None, :] - 2.0 * x_local @ block.T,
                0.0,
            )
            dist = jnp.where(ids[None, :] == my_ids[:, None], jnp.inf, dist)
            neg, top = jax.lax.top_k(-dist, k)
            cand_i = ids[top]
            nld, nli, _ = _merge_topk(nl_d, nl_i, -neg, cand_i, k)
            block = jax.lax.ppermute(block, axis, _ring_perm(axis, P_))
            block2 = jax.lax.ppermute(block2, axis, _ring_perm(axis, P_))
            return nld, nli, block, block2

        nl_d, nl_i, _, _ = jax.lax.fori_loop(
            0, P_, step, (nl_d, nl_i, x_local, x2_local)
        )
        return nl_d, nl_i

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=(P(axis, None), P(axis, None)),
    )
    return fn(x)


def _merge_topk(nl_d, nl_i, cand_d, cand_i, k):
    nl = NeighborLists(nl_d, nl_i, jnp.zeros_like(nl_i, dtype=bool))
    merged, upd = heap.merge(nl, cand_d, cand_i, cand_new=False)
    return merged.dist, merged.idx, upd


def _fetch_features_ring(x_local, needed_ids, axis: str, P_: int, n_local: int):
    """Gather features for arbitrary global ids via the feature ring.
    needed_ids: (m,) int32 (clipped >=0); returns (m, d) rows."""
    m = needed_ids.shape[0]
    d = x_local.shape[1]
    p = jax.lax.axis_index(axis)
    out = jax.lax.pvary(jnp.zeros((m, d), x_local.dtype), (axis,))

    def step(s, carry):
        out, block = carry
        owner = (p - s) % P_
        local = needed_ids - owner * n_local
        hit = (local >= 0) & (local < n_local)
        rows = block[jnp.clip(local, 0, n_local - 1)]
        out = jnp.where(hit[:, None], rows, out)
        block = jax.lax.ppermute(block, axis, _ring_perm(axis, P_))
        return out, block

    out, _ = jax.lax.fori_loop(0, P_, step, (out, x_local))
    return out


def fetch_rows_a2a(x_local, ids, *, axis: str, P_: int, n_local: int,
                   cap: int):
    """Request-routed feature fetch (§Perf iteration on the ring fetch).

    The ring fetch rewrites the whole (m, d) output buffer P times —
    O(P*m*d) HBM traffic. Here each shard instead SENDS its needed ids to
    their owners (one all_to_all of (P, cap) ids), owners gather rows
    locally, and one reverse all_to_all returns them in the same bucket
    positions — O(cap*P*d) traffic total, independent of P's effect on
    passes. Overflow beyond ``cap`` per destination is dropped and
    reported in the returned mask (sampling noise, like every other
    bounded buffer in NN-Descent).

    Returns (rows (m, d), ok (m,) bool).
    """
    p = jax.lax.axis_index(axis)
    base = p * n_local
    valid = ids >= 0
    dest = jnp.where(valid, jnp.clip(ids // n_local, 0, P_ - 1), P_)
    pos = _bucket_slots(dest, P_)
    req = jnp.full((P_, cap), -1, jnp.int32)
    req = req.at[dest, pos].set(ids, mode="drop")

    got = jax.lax.all_to_all(req[:, None, :], axis, split_axis=0,
                             concat_axis=0, tiled=False)[:, 0, :]
    # rows requested FROM me (global ids owned here; -1 = empty slot)
    loc = got - base
    ok_here = (loc >= 0) & (loc < n_local)
    rows = x_local[jnp.clip(loc, 0, n_local - 1)]
    zero = jnp.zeros((), x_local.dtype)       # dtype-safe fill (works for
    rows = jnp.where(ok_here[..., None], rows, zero)     # int rows too)
    back = jax.lax.all_to_all(rows[:, None], axis, split_axis=0,
                              concat_axis=0, tiled=False)[:, 0]

    ok = valid & (pos < cap)
    fetched = back[jnp.clip(dest, 0, P_ - 1), jnp.clip(pos, 0, cap - 1)]
    return jnp.where(ok[:, None], fetched, zero), ok


def _bucket_slots(dest: jax.Array, P_: int):
    """Each row's slot in its destination's bucket: its rank among the
    rows bound for the same shard, in row order (``dest == P_``: no
    destination, slot past every bucket). Prefix sums over the P
    destinations, where a sort by destination compiled for tens of
    seconds on TPU."""
    hit = dest[None, :] == jnp.arange(P_, dtype=dest.dtype)[:, None]
    rank = jnp.cumsum(hit.astype(jnp.int32), axis=1) - 1          # (P, m)
    pos = jnp.sum(jnp.where(hit, rank, 0), axis=0)
    return jnp.where(dest < P_, pos, dest.shape[0])


def nn_descent_sharded_iteration(
    key: jax.Array,
    x_local: jax.Array,       # (n_local, d)
    x2_local: jax.Array,      # (n_local,)
    nl: NeighborLists,        # local rows, GLOBAL neighbor ids
    cfg: DescentConfig,
    *,
    axis: str,
    P_: int,
    fetch: str = "a2a",       # a2a (optimized) | ring (baseline)
):
    """One sharded NN-Descent iteration (call under shard_map)."""
    n_local, k = nl.idx.shape
    p = jax.lax.axis_index(axis)
    base = p * n_local

    # ---- selection runs on LOCAL receiver rows; incidences whose receiver
    # is remote are routed by all_to_all before compaction.
    local_nl = NeighborLists(nl.dist, nl.idx, nl.new)
    recv, cand, is_new, valid, is_fwd, slot = selection._incidences(local_nl)
    # forward incidences: receiver = local row (global id base+row).
    half = n_local * k
    recv = jnp.concatenate(
        [base + recv[:half], recv[half:]]  # second half already global ids
    )
    cand = jnp.concatenate([cand[:half], base + cand[half:]])

    # turbosampling accept, per pool as in selection.selection_turbo:
    # a local receiver's new/old degrees are counted over the incidences
    # this shard generated for it (its forward slots and the reverse
    # incidences of local rows — all of them on cluster-aligned shards);
    # a remote receiver's degree is not known here, so its incidences
    # accept at rho_k / 2k
    owner_rows = recv - base
    in_local = (owner_rows >= 0) & (owner_rows < n_local)
    seg = jnp.where(in_local, owner_rows, n_local)

    def p_edge(mask):
        deg = jax.ops.segment_sum(mask.astype(jnp.int32), seg,
                                  num_segments=n_local + 1)[:n_local]
        p_local = jnp.minimum(1.0, cfg.rho_k / jnp.maximum(deg, 1))
        return jnp.where(in_local, p_local[jnp.clip(owner_rows, 0,
                                                    n_local - 1)],
                         cfg.rho_k / (2.0 * k))

    k_acc, k_rnd, key = jax.random.split(key, 3)
    u = jax.random.uniform(k_acc, recv.shape)
    acc_new = valid & is_new & (u < p_edge(valid & is_new))
    acc_old = valid & ~is_new & (u < p_edge(valid & ~is_new))

    # route accepted incidences to receiver owners
    def route(acc_mask):
        return _all_to_all_route(
            (recv, cand), acc_mask, recv // n_local, P_, axis)

    got_new = route(acc_new)        # 2 x (P_*m,) rows targeting me
    got_old = route(acc_old)

    def compact(got, c):
        r, got_c = got
        valid_r = r >= 0
        rl = jnp.where(valid_r, r - base, -1)
        rnd = jax.random.uniform(jax.random.fold_in(key, c), r.shape)
        from repro.core.selection import _compact
        return _compact(rl, got_c, valid_r, rnd, n_local, c)

    cand_new = compact(got_new, cfg.rho_k)
    cand_old = compact(got_old, cfg.rho_k)

    # clear sampled forward flags (local slots whose incidence was accepted)
    sampled = jnp.zeros((n_local * k,), bool)
    fwd_acc = acc_new[:half] & is_fwd[:half]
    sampled = sampled.at[jnp.where(fwd_acc, slot[:half], 0)].max(fwd_acc)
    nl = heap.mark_sampled_old(nl, sampled.reshape(n_local, k))

    # ---- fetch candidate features, evaluate pair distances
    cn, co = cand_new, cand_old
    flat = jnp.concatenate([flatten_rows(cn), flatten_rows(co)])
    if fetch == "a2a":
        cap_f = flat.shape[0]
        feats, fok = fetch_rows_a2a(
            x_local, flat, axis=axis, P_=P_, n_local=n_local, cap=cap_f)
        # candidates whose fetch overflowed the bucket: invalidate
        okn = fok[: cn.size].reshape(cn.shape)
        oko = fok[cn.size:].reshape(co.shape)
        cn = jnp.where(okn, cn, -1)
        co = jnp.where(oko, co, -1)
    else:
        feats = _fetch_features_ring(
            x_local, jnp.clip(flat, 0, P_ * n_local - 1), axis, P_, n_local
        )
    d_feat = feats.shape[1]
    xg_n = feats[: cn.size].reshape(n_local, -1, d_feat)
    xg_o = feats[cn.size :].reshape(n_local, -1, d_feat)
    x2_n = jnp.sum(xg_n * xg_n, axis=-1)
    x2_o = jnp.sum(xg_o * xg_o, axis=-1)
    vn, vo = cn >= 0, co >= 0

    d_nn = pair_block(xg_n, x2_n, xg_n, x2_n)
    d_no = pair_block(xg_n, x2_n, xg_o, x2_o)

    cn_b, co_b = cn.shape[1], co.shape[1]
    iu = jnp.triu_indices(cn_b, k=1)
    a_nn, b_nn = cn[:, iu[0]], cn[:, iu[1]]
    dd_nn = d_nn[:, iu[0], iu[1]]
    ok_nn = vn[:, iu[0]] & vn[:, iu[1]] & (a_nn != b_nn)
    a_no = jnp.broadcast_to(cn[:, :, None], (n_local, cn_b, co_b)).reshape(n_local, -1)
    b_no = jnp.broadcast_to(co[:, None, :], (n_local, cn_b, co_b)).reshape(n_local, -1)
    dd_no = d_no.reshape(n_local, -1)
    ok_no = (
        jnp.broadcast_to(vn[:, :, None], (n_local, cn_b, co_b)).reshape(n_local, -1)
        & jnp.broadcast_to(vo[:, None, :], (n_local, cn_b, co_b)).reshape(n_local, -1)
        & (a_no != b_no)
    )
    a = flatten_rows(jnp.concatenate([a_nn, b_nn, a_no, b_no], axis=1))
    b = flatten_rows(jnp.concatenate([b_nn, a_nn, b_no, a_no], axis=1))
    dd = flatten_rows(jnp.concatenate([dd_nn, dd_nn, dd_no, dd_no], axis=1))
    ok = flatten_rows(jnp.concatenate([ok_nn, ok_nn, ok_no, ok_no], axis=1))

    # ---- route updates to receiver owners, merge locally. The received
    # (receiver, candidate, dist) rows go through the fused knn_join
    # routing (invert incidences -> gather -> top-merge_k select) instead
    # of a (receiver, dist) lexsort — the same kernel family as the
    # single-chip local join.
    r, got_b, got_d = _all_to_all_route(
        (a, b, _f32_bits(dd)), ok, a // n_local, P_, axis)
    valid_r = r >= 0
    rl = jnp.where(valid_r, r - base, -1)
    dd_r = jnp.where(valid_r, _bits_f32(got_d), jnp.inf)
    # per-receiver source buffer: 2x the expected load (cap_u routes
    # ~4*merge_k rows per receiver on average). Overflow drops the
    # FARTHEST incoming rows per receiver (distance-prioritized, closing
    # the ROADMAP watch item); hub-heavy meshes can still raise
    # DescentConfig.join_src to widen the buffer.
    s_cap = cfg.join_src or 8 * cfg.merge_k
    rows_of, _ = invert_candidates(
        rl[:, None], n_local, s_cap, prio=dd_r[:, None]
    )
    ok_r = rows_of >= 0
    safe_r = jnp.where(ok_r, rows_of, 0)
    gd = jnp.where(ok_r, dd_r[safe_r], jnp.inf)
    gi = jnp.where(ok_r, got_b[safe_r], -1)
    cd, ci = ops.knn_join_select(
        gd, gi, jnp.full((n_local,), jnp.inf), c=cfg.merge_k,
        backend=cfg.backend,
    )
    nl, upd = heap.merge(nl, cd, ci, cand_new=True)
    n_evals = jnp.sum(ok_nn) + jnp.sum(ok_no)
    total_upd = jax.lax.psum(jnp.sum(upd), axis)
    total_ev = jax.lax.psum(n_evals, axis)
    return nl, total_upd, total_ev


def polish_sharded_round(
    x_local: jax.Array,       # (n_local, d) f32
    x2_local: jax.Array,      # (n_local,)
    nl: NeighborLists,        # local rows, GLOBAL neighbor ids
    *,
    axis: str,
    P_: int,
    merge_c: int,             # select width before the merge (<= k*k)
    backend: str = "auto",    # kernel dispatch (DescentConfig.backend)
):
    """One sharded exhaustive local-join polish round (call under
    shard_map) — the port of core/nn_descent.py polish_iteration: every
    local row joins against ALL k*k of its neighbors-of-neighbors
    (forward direction, unsampled). Neighbor LISTS of remote neighbors
    and then the candidates' FEATURES are both fetched with the
    request-routed all_to_all (``fetch_rows_a2a``); candidates whose
    fetch overflowed its bucket are dropped (bounded-buffer sampling
    noise). The k*k candidate row is reduced by the fused
    ``knn_join_select`` kernel before the bounded merge, exactly like the
    single-chip fused polish. Returns (nl, accepted, evals) — the counts
    psum'd over the mesh."""
    n_local, k = nl.idx.shape
    p = jax.lax.axis_index(axis)
    my_ids = p * n_local + jnp.arange(n_local, dtype=jnp.int32)

    def chunk(args):
        xr, x2r, myr, dr, ir, fr = args
        # the chunk's neighbor lists, then its candidates' features; one
        # bucket per destination holds the whole chunk's requests (no
        # overflow, whatever the shard layout). Whole, the feature
        # buckets were P * n_local * k*k * d floats (~27 GB at 32,768
        # rows/chip, k=20, d=128), and the (n_local*k, k) list fetch
        # compiled for minutes on TPU
        lists, ok_l = fetch_rows_a2a(
            nl.idx, ir.reshape(-1), axis=axis, P_=P_, n_local=n_local,
            cap=ir.size,
        )                                                 # (rows*k, k)
        nb = lists.reshape(-1, k * k)
        okr = jnp.repeat((ir >= 0) & ok_l.reshape(ir.shape), k, axis=1)
        feats, ok_f = fetch_rows_a2a(
            x_local, nb.reshape(-1), axis=axis, P_=P_, n_local=n_local,
            cap=nb.size,
        )                                                 # (rows*k*k, d)
        okr = okr & (nb >= 0) & ok_f.reshape(nb.shape) & (nb != myr[:, None])
        feats = feats.reshape(nb.shape + (-1,))
        dd = x2r[:, None] + jnp.sum(feats * feats, axis=-1) - 2.0 * (
            jnp.einsum("nd,ncd->nc", xr, feats,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        )
        dd = jnp.where(okr, jnp.maximum(dd, 0.0), jnp.inf)
        cd, ci = ops.knn_join_select(
            dd, jnp.where(okr, nb, -1), dr[:, -1], c=merge_c,
            backend=backend,
        )
        sub, upd = heap.merge(NeighborLists(dr, ir, fr), cd, ci)
        return sub, upd, jnp.sum(okr, axis=1)

    nl, upd, evals = chunked_rows(
        chunk, (x_local, x2_local, my_ids, *nl), min(512, n_local))
    return (nl, jax.lax.psum(jnp.sum(upd), axis),
            jax.lax.psum(jnp.sum(evals), axis))


@dataclasses.dataclass(frozen=True)
class BreakerConfig:
    """Knobs for the per-shard latency circuit breaker."""
    alpha: float = 0.3        # EWMA weight of the newest latency sample
    trip_ratio: float = 3.0   # open when ewma > ratio * median(others)
    min_samples: int = 3      # samples before a shard is allowed to trip
    probe_every: int = 4      # while open, probe every N dispatches
    recover_ratio: float = 1.5
    #                         # a half-open probe closes the breaker when
    #                         # its sample <= ratio * median(others)


class ShardBreaker:
    """Per-shard latency circuit breaker for ``graph_search_sharded``.

    A shard that is chronically SLOW (overloaded host, thermal throttle,
    degraded link) is worse than a dead one: it drags every dispatch's
    tail latency while contributing nothing a survivor could not. The
    breaker watches a per-shard latency EWMA; when a shard's EWMA
    exceeds ``trip_ratio`` x the median of the other shards' EWMAs (a
    scale-invariant trip — no wall-clock constant to mistune), the
    breaker OPENS and the shard is handed to the PR-8 ``dead_shards``
    degraded-merge path: answers keep flowing from survivors, recall
    degrades, nothing stalls. While open, every ``probe_every``-th
    dispatch is a HALF-OPEN probe: the shard is re-included once, and a
    healthy sample (<= ``recover_ratio`` x the others' median) closes
    the breaker again.

    The breaker is deliberately clock-free: it consumes latency samples
    via :meth:`observe` and never reads ``time`` itself, so tests drive
    it with synthetic numbers and the ``shard.degrade`` fault site
    (``core/faults.py``) inflates real samples deterministically —
    trip/probe/recover are all exercisable without a slow device. One
    :meth:`excluded` + one :meth:`observe` pair per dispatch;
    ``graph_search_sharded(breaker=...)`` does both.

    The breaker never excludes EVERY shard: with all breakers open the
    least-bad shard (lowest EWMA) stays in the dispatch, so serving can
    never self-inflict the all-dead empty answer.
    """

    def __init__(self, n_shards: int, cfg: BreakerConfig | None = None):
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.n_shards = n_shards
        self.cfg = cfg or BreakerConfig()
        self.ewma: list = [None] * n_shards
        self.samples = [0] * n_shards
        self.open = [False] * n_shards
        self._opened_at = [0] * n_shards    # dispatch counter at open
        self._probing: set = set()          # half-open this dispatch
        self.dispatches = 0
        self.trips = 0
        self.probes = 0
        self.recoveries = 0

    def _median_others(self, shard: int):
        vals = sorted(
            e for s, e in enumerate(self.ewma)
            if s != shard and e is not None and not self.open[s]
        )
        if not vals:
            return None
        return vals[len(vals) // 2]

    def excluded(self) -> list:
        """Shards to treat as dead for the NEXT dispatch (advances the
        dispatch counter; open shards due for their half-open probe are
        re-included and remembered as probing)."""
        self.dispatches += 1
        self._probing = set()
        out = []
        for s in range(self.n_shards):
            if not self.open[s]:
                continue
            age = self.dispatches - self._opened_at[s]
            if age > 0 and age % max(1, self.cfg.probe_every) == 0:
                self._probing.add(s)        # half-open: let one through
                self.probes += 1
            else:
                out.append(s)
        if len(out) == self.n_shards:       # never exclude every shard
            best = min(out, key=lambda s: self.ewma[s] or 0.0)
            out.remove(best)
        return out

    def observe(self, latencies) -> None:
        """Fold per-shard latency samples (seconds) from the dispatch
        that :meth:`excluded` opened. ``latencies``: {shard: seconds} —
        excluded shards simply have no entry. Closed shards update their
        EWMA and may trip; probing shards close on a healthy sample and
        re-arm the probe timer otherwise."""
        a = self.cfg.alpha
        for s, lat in dict(latencies).items():
            s = int(s)
            if not (0 <= s < self.n_shards):
                continue
            lat = float(lat)
            prev = self.ewma[s]
            self.ewma[s] = lat if prev is None else (1 - a) * prev + a * lat
            self.samples[s] += 1
            med = self._median_others(s)
            if self.open[s]:
                if s in self._probing and med is not None \
                        and lat <= self.cfg.recover_ratio * med:
                    self.open[s] = False
                    self.recoveries += 1
                    # forget the degraded EWMA: the shard re-enters on
                    # probation with its healthy probe sample
                    self.ewma[s] = lat
                    self.samples[s] = 1
                else:
                    self._opened_at[s] = self.dispatches
            elif (self.samples[s] >= self.cfg.min_samples
                  and med is not None
                  and self.ewma[s] > self.cfg.trip_ratio * med):
                self.open[s] = True
                self._opened_at[s] = self.dispatches
                self.trips += 1
        self._probing = set()

    def stats(self) -> dict:
        return {
            "dispatches": self.dispatches,
            "open_shards": [s for s in range(self.n_shards)
                            if self.open[s]],
            "ewma": [None if e is None else float(e) for e in self.ewma],
            "trips": self.trips,
            "probes": self.probes,
            "recoveries": self.recoveries,
        }


# graph_search_sharded's shard_map bodies run under jit with their static
# values as arguments: called bare, a shard_map body runs primitive by
# primitive, a compile each.

@functools.partial(jax.jit, static_argnames=("mesh", "axis", "k_out", "cfg"))
def _search_replicated(key, x, graph_idx, queries, live, *, mesh, axis,
                       k_out, cfg):
    """Replicated dispatch: every shard searches every query; one
    all_gather + top-k folds the per-shard lists of the live shards."""
    n_local = x.shape[0] // mesh.shape[axis]

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(axis, None), P(axis, None), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def fn(key, x_local, gi_local, q, live):
        p = jax.lax.axis_index(axis)
        base = p * n_local
        kk = jax.random.fold_in(key, p)
        d, i = graph_search(x_local, gi_local, q, k_out=k_out, key=kk,
                            cfg=cfg)
        gi = jnp.where(i >= 0, base + i, -1)
        ds = jax.lax.all_gather(d, axis)             # (P, q, k_out)
        is_ = jax.lax.all_gather(gi, axis)
        # survivors-only merge: a dead shard's gathered list is
        # masked out wholesale before the top-k fold
        ds = jnp.where(live[:, None, None], ds, jnp.inf)
        is_ = jnp.where(live[:, None, None], is_, -1)
        alld = jnp.moveaxis(ds, 0, 1).reshape(q.shape[0], -1)
        alli = jnp.moveaxis(is_, 0, 1).reshape(q.shape[0], -1)
        alld = jnp.where(alli >= 0, alld, jnp.inf)
        neg, pos = jax.lax.top_k(-alld, k_out)
        out_i = jnp.take_along_axis(alli, pos, axis=1)
        return jnp.where(out_i >= 0, -neg, jnp.inf), out_i

    return fn(key, x, graph_idx, queries, live)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "k_out",
                                             "cap_q", "e_w", "cfg"))
def _search_routed(key, x, graph_idx, queries, top_shards, entg, live, *,
                   mesh, axis, k_out, cap_q, e_w, cfg):
    """Routed dispatch: each shard searches the queries routed to it from
    a (cap_q, d) buffer, seeded from ``entg``; each query folds only its
    route_p shard lists."""
    n_local = x.shape[0] // mesh.shape[axis]
    q_n = queries.shape[0]

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(axis, None), P(axis, None), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    def fn_routed(key, x_local, gi_local, q, tsh, eg, live):
        p = jax.lax.axis_index(axis)
        base = p * n_local
        kk = jax.random.fold_in(key, p)
        # queries routed to this shard, compacted into a cap_q buffer;
        # a dead shard searches nothing (and its gathered buffer is
        # excluded from every query's partial merge below)
        mine = (tsh == p).any(axis=1) & live[p]           # (q,)
        qids = jnp.nonzero(mine, size=cap_q, fill_value=-1)[0]
        qids = qids.astype(jnp.int32)
        ok_q = qids >= 0
        safe_q = jnp.where(ok_q, qids, 0)
        qsel = q[safe_q]                                  # (cap_q, d)
        # this shard's slice of the routed entry candidates, local ids,
        # valid entries compacted to the front (stable argsort)
        egl = eg[safe_q] - base                           # (cap_q, t*m)
        w = egl.shape[1]
        ve = ok_q[:, None] & (eg[safe_q] >= 0) & (egl >= 0) & (egl < n_local)
        ar = jnp.arange(w, dtype=jnp.int32)[None, :]
        order = jnp.argsort(jnp.where(ve, ar, w + ar), axis=1)
        ent = jnp.take_along_axis(jnp.where(ve, egl, -1), order, axis=1)
        if w >= e_w:
            ent = ent[:, :e_w]
        else:
            ent = jnp.pad(ent, ((0, 0), (0, e_w - w)), constant_values=-1)
        # holes (few/no router members on this shard) fall back to a
        # shard-local keyed draw — same no-replacement draw as the
        # single-chip path
        rnd = jax.lax.top_k(
            jax.random.uniform(kk, (n_local,)), e_w
        )[1].astype(jnp.int32)
        ent = jnp.where(ent >= 0, ent, rnd[None, :])
        d, i = graph_search(x_local, gi_local, qsel, k_out=k_out,
                            entry=ent, key=kk, cfg=cfg)
        gi = jnp.where((i >= 0) & ok_q[:, None], base + i, -1)
        d = jnp.where(gi >= 0, d, jnp.inf)
        # inverse map: query id -> its slot in this shard's buffer
        gpos = jnp.full((q_n,), -1, jnp.int32).at[
            jnp.where(ok_q, qids, q_n)
        ].set(jnp.arange(cap_q, dtype=jnp.int32), mode="drop")
        ds = jax.lax.all_gather(d, axis)                  # (P, cap_q, k)
        is_ = jax.lax.all_gather(gi, axis)
        gp = jax.lax.all_gather(gpos, axis)               # (P, q)
        # partial merge: each query folds ONLY its route_p shard lists
        pp = gp[tsh, jnp.arange(q_n)[:, None]]            # (q, p)
        ppc = jnp.clip(pp, 0, cap_q - 1)
        cd = ds[tsh, ppc]                                 # (q, p, k_out)
        ci = is_[tsh, ppc]
        hit = (pp >= 0)[:, :, None] & (ci >= 0) & live[tsh][:, :, None]
        cd = jnp.where(hit, cd, jnp.inf).reshape(q_n, -1)
        ci = jnp.where(hit, ci, -1).reshape(q_n, -1)
        neg, pos = jax.lax.top_k(-cd, k_out)
        out_i = jnp.take_along_axis(ci, pos, axis=1)
        out_d = jnp.where(out_i >= 0, -neg, jnp.inf)
        searched = jax.lax.psum(jnp.sum(ok_q.astype(jnp.int32)), axis)
        routed_q = jax.lax.psum(jnp.sum(mine.astype(jnp.int32)), axis)
        return out_d, out_i, searched, routed_q

    return fn_routed(key, x, graph_idx, queries, top_shards, entg, live)


def _static_cfg(cfg: SearchConfig) -> SearchConfig:
    """The config as a cache key: the per-dispatch deadline is a host
    knob that traced searches ignore, so it must not split the cache."""
    return dataclasses.replace(cfg, max_rounds_deadline=0.0)


def graph_search_sharded(
    mesh: Mesh,
    x: jax.Array,           # (n, d) corpus, sharded by rows over ``axis``
    graph_idx: jax.Array,   # (n, k) per-shard subgraph, LOCAL neighbor ids
    queries: jax.Array,     # (q, d) replicated query batch
    *,
    k_out: int = 10,
    cfg: SearchConfig | None = None,
    key: jax.Array | None = None,
    axis: str = "data",
    router=None,            # core/router.Router over the GLOBAL corpus
    route_p: int = 0,       # shards searched per query (0 = all: legacy)
    route_cap: int = 0,     # per-shard routed-query buffer (0 = auto)
    with_stats: bool = False,
    dead_shards=None,       # shard indices known unavailable (timed-out
    #                         or lost); merged with any active FaultPlan
    #                         ("shard.dead"/"shard.slow" sites)
    breaker: ShardBreaker | None = None,
    #                         # latency circuit breaker: its open shards
    #                         # join ``dead`` for this dispatch, and the
    #                         # dispatch's wall time feeds back into its
    #                         # per-shard EWMAs (see _breaker_feed)
):
    """Sharded serving entry for the fused batched search: corpus rows are
    sharded over the mesh's ``axis``; each shard holds a K-NN subgraph
    over its OWN rows (neighbor ids are shard-local — e.g. each shard's
    slice built independently, or a global build restricted to local
    edges). Every query block runs the shard-local fused search
    (core/graph_search.py — the per-shard call is the same jitted blocked
    multi-expansion path as the single-chip entry), local hits are lifted
    to global ids (shard * n_local + row).

    **Replicated dispatch** (``route_p=0`` or no ``router``): every query
    searches every shard, one all_gather + top-k folds the P per-shard
    lists — per-query work is O(P).

    **Routed dispatch** (``router`` over the global corpus + 0 < route_p
    < P): centroid→shard affinity (the minimum query-centroid distance
    among each shard's centroids, shard of a centroid = majority shard of
    its member rows) picks the top-``route_p`` shards per query; each
    shard searches only the queries routed to it, from a compacted
    (route_cap, ·) buffer, seeded with the router's member rows that live
    on that shard (holes fall back to a shard-local random draw). The
    all_gather moves (P, route_cap, k_out) compacted buffers instead of
    (P, q, k_out), and the partial merge folds only each query's
    ``route_p`` shard lists — per-query distance work drops from P shards
    to p. ``route_cap`` bounds per-shard load (default ~4x the balanced
    expectation); overflow queries lose that shard's contribution
    (bounded-buffer sampling noise; ``with_stats`` exposes the drop
    count).

    ``cfg.precision`` threads straight through: with "int8"/"bf16" each
    shard quantizes its LOCAL rows inside the shard_map body and runs the
    two-stage scoring + fp32 re-rank per shard, so the gathered per-shard
    distances are already exact fp32 and the global top-k merge needs no
    precision awareness at all. (Serving loops that re-search a static
    sharded corpus should hoist the per-shard quantization into a cached
    mirror like MutableKNNStore does; this entry re-quantizes per call.)

    **Degraded dispatch** (``dead_shards`` non-empty, or a FaultPlan
    marks shards dead/slow-past-timeout): the driver re-merges from the
    SURVIVING shards instead of raising — replicated dispatch drops the
    dead shards' gathered lists before the top-k fold; routed dispatch
    re-routes by pushing dead shards' affinity to +inf, so each query's
    top-``route_p`` set prefers live shards (a dead shard that still
    lands in the set, e.g. route_p > live shards, contributes nothing to
    the merge). Stats gain ``degraded_shards`` (the dead list) and
    ``cover_frac`` (the fraction of per-query shard work answered by
    live shards: live/P replicated; routed, the mean liveness of each
    query's PRE-reroute affinity set — the post-reroute set is all-live
    by construction). All shards dead answers every query empty — degraded
    recall, never an exception.

    ``cfg.metric`` selects the distance ("l2" | "cosine" | "mips") the
    same way the single-chip entry does: the CORPUS must already be
    transformed (rows normalized for cosine; the sqrt(M^2 - |x|^2)
    augmented coordinate appended for MIPS — build the sharded corpus
    through ``core.metric.transform_corpus`` before slicing it over the
    mesh), and this driver applies the matching QUERY-side transform
    once, before admission/routing, so the per-shard fused searches and
    the global top-k merge stay pure squared-l2. Returned distances are
    transformed-space l2 — convert with
    ``core.metric.similarity_from_dist`` when native-metric scores are
    needed.

    Returns (dist (q, k_out), idx (q, k_out) global ids), replicated —
    plus a stats dict (fanout/shards/routed/searched/dropped queries)
    when ``with_stats``.
    """
    from repro.core import metric as metric_mod
    from repro.core.graph_search import _admit_queries, _batch_key, \
        _mask_bad_rows
    cfg = cfg or SearchConfig()
    # query-side metric transform runs HERE (driver level): per-shard
    # graph_search calls re-apply it, which is a no-op by construction
    # (normalization is idempotent; MIPS queries are already at the
    # augmented width so the zero-pad branch never fires again)
    if cfg.metric == "cosine":
        queries = metric_mod.normalize_rows(queries.astype(jnp.float32))
    elif cfg.metric == "mips" and queries.ndim == 2 \
            and queries.shape[1] < x.shape[1]:
        queries = jnp.pad(
            queries, ((0, 0), (0, x.shape[1] - queries.shape[1])))
    else:
        metric_mod.check_metric(cfg.metric)
    # admission runs HERE, on the concrete batch — graph_search inside
    # the shard_map bodies sees tracers and skips its own check
    queries, bad_rows = _admit_queries(queries, x.shape[1], cfg.strict)
    # no shared-constant entry fallback (same contract as graph_search):
    # keyless calls derive the entry key from the query batch content, so
    # repeated serving batches don't reuse identical per-shard entries
    key = _batch_key(queries) if key is None else key
    P_ = mesh.shape[axis]
    n = x.shape[0]
    assert n % P_ == 0, (n, P_)
    n_local = n // P_
    dead_set = {int(s) for s in (dead_shards or ())
                if 0 <= int(s) < P_} | set(faults.dead_shards(P_))
    if breaker is not None:
        # one excluded()/observe() pair per dispatch: open shards join
        # the degraded-merge path exactly like dead ones
        dead_set |= set(breaker.excluded())
    dead = sorted(dead_set)
    live_mask = jnp.ones((P_,), bool)
    if dead:
        live_mask = live_mask.at[jnp.asarray(dead, jnp.int32)].set(False)
    n_live = P_ - len(dead)
    # the subgraph contract is checkable and cheap to check (this is a
    # python-level driver): GLOBAL ids — e.g. build_knn_graph_sharded
    # output fed in directly — would be silently clipped into garbage
    # adjacency inside the shard-local search
    if int(jnp.max(graph_idx)) >= n_local:
        raise ValueError(
            f"graph_idx holds ids >= n_local ({n_local}): "
            "graph_search_sharded expects shard-LOCAL neighbor ids (each "
            "shard's subgraph over its own rows), not the global ids "
            "build_knn_graph_sharded emits — subtract each shard's base "
            "(shard * n_local) and drop cross-shard edges first"
        )
    routed = router is not None and 0 < route_p < P_
    if not routed:
        t0 = time.monotonic()
        out_d, out_i = _search_replicated(
            key, x, graph_idx, queries, live_mask, mesh=mesh, axis=axis,
            k_out=k_out, cfg=_static_cfg(cfg))
        if breaker is not None:
            jax.block_until_ready(out_d)
            _breaker_feed(breaker, time.monotonic() - t0, P_, dead)
        out_d, out_i = _mask_bad_rows(out_d, out_i, bad_rows)
        if with_stats:
            q_n = queries.shape[0]
            stats = {
                "fanout": P_, "shards": P_,
                "routed_queries": q_n * n_live,
                "searched_queries": q_n * n_live, "dropped_queries": 0,
                "degraded_shards": dead,
                "cover_frac": n_live / P_,
            }
            if breaker is not None:
                stats["breaker"] = breaker.stats()
            return out_d, out_i, stats
        return out_d, out_i

    # ---- routed dispatch: replicated precompute (one small centroid
    # tile per batch), then a compacted per-shard search + partial merge
    q_n = queries.shape[0]
    qf = queries.astype(jnp.float32)
    dqc = ops.pairwise_sq_l2(qf, router.centroids, backend=cfg.backend)
    # shard of a centroid = majority shard of its member rows (centroids
    # live in feature space, not the id space — members pin them down)
    mem = router.members.idx                              # (c, m)
    ms = jnp.where(mem >= 0, mem // n_local, -1)
    votes = (ms[:, :, None] == jnp.arange(P_)[None, None, :]).sum(1)
    shard_of = jnp.argmax(votes, axis=1)                  # (c,)
    # query→shard affinity: best centroid distance among the shard's
    # centroids (+inf for shards that own no centroid)
    aff = jax.ops.segment_min(dqc.T, shard_of, num_segments=P_).T  # (q, P)
    # re-route past dead shards: +inf affinity pushes them out of every
    # query's top-p set whenever enough live shards exist. cover_frac
    # reports against the PRE-reroute affinity set (the shards the
    # query wanted) — the post-reroute set is all-live by construction.
    _, want_shards = jax.lax.top_k(-aff, route_p)         # (q, p)
    aff = jnp.where(live_mask[None, :], aff, jnp.inf)
    _, top_shards = jax.lax.top_k(-aff, route_p)          # (q, p)
    t = min(cfg.router_t, router.centroids.shape[0])
    _, top_cent = jax.lax.top_k(-dqc, t)                  # (q, t)
    # per-query entry candidates, nearest-member-major (global ids)
    entg = jnp.moveaxis(mem[top_cent], 1, 2).reshape(q_n, -1)  # (q, t*m)
    e_w = min(cfg.beam, n_local)
    cap_q = route_cap or min(
        q_n, max(32, -((-4 * q_n * route_p) // P_))
    )
    cap_q = min(cap_q, q_n)

    t0 = time.monotonic()
    out_d, out_i, searched, routed_q = _search_routed(
        key, x, graph_idx, queries, top_shards, entg, live_mask, mesh=mesh,
        axis=axis, k_out=k_out, cap_q=cap_q, e_w=e_w, cfg=_static_cfg(cfg))
    if breaker is not None:
        jax.block_until_ready(out_d)
        _breaker_feed(breaker, time.monotonic() - t0, P_, dead)
    out_d, out_i = _mask_bad_rows(out_d, out_i, bad_rows)
    if with_stats:
        stats = {
            "fanout": route_p, "shards": P_,
            "routed_queries": int(routed_q),
            "searched_queries": int(searched),
            "dropped_queries": int(routed_q) - int(searched),
            "degraded_shards": dead,
            "cover_frac": float(jnp.mean(
                live_mask[want_shards].astype(jnp.float32))),
        }
        if breaker is not None:
            stats["breaker"] = breaker.stats()
        return out_d, out_i, stats
    return out_d, out_i


def _breaker_feed(breaker: ShardBreaker, dt: float, P_: int, dead) -> None:
    """Attribute one dispatch's wall time to its live shards and fold the
    samples into the breaker. A fused shard_map dispatch yields no
    per-shard clocks, so the driver charges every live shard the total
    wall time — neutral for the ratio-based trip (uniform samples move
    every EWMA identically); the deterministic skew comes from the
    ``shard.degrade`` fault site, which inflates specific shards'
    samples. Deployments with per-shard RPC timings should skip this
    helper and call ``breaker.observe`` with the real per-shard numbers.
    """
    dead = set(dead)
    lat = {s: dt for s in range(P_) if s not in dead}
    for s, f in faults.degrade_factors(P_).items():
        if s in lat:
            lat[s] *= f
    breaker.observe(lat)


def _f32_bits(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _bits_f32(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.int32), jnp.float32)


def _all_to_all_route(cols, mask, dest, P_, axis):
    """Route the rows of ``cols`` (a tuple of (m,) int32 columns) whose
    ``mask`` is set to shard ``dest`` (m,) over ``axis``. Every bucket
    holds all m rows, so nothing overflows: with cluster-aligned shards
    nearly every row stays local, and buckets sized for a uniform spread
    (m/P) dropped most of them. Returns the received columns, each
    (P_*m,), invalid rows marked by -1 in the first. Each column travels
    as its own flat array: a stacked (m, w) payload is a narrow array
    whose gathers and relayouts compile for minutes on TPU."""
    m = dest.shape[0]
    dest = jnp.where(mask, dest, P_)
    pos = _bucket_slots(dest, P_)
    out = []
    for col in cols:
        buckets = jnp.full((P_, m), -1, dtype=col.dtype)
        buckets = buckets.at[dest, pos].set(col, mode="drop")
        got = jax.lax.all_to_all(buckets, axis, split_axis=0,
                                 concat_axis=0, tiled=False)
        out.append(got.reshape(P_ * m))
    return tuple(out)


def make_sharded_iteration_lowerable(mesh: Mesh, *, n: int, d: int, k: int,
                                     rho: float = 1.0,
                                     fetch: str = "a2a"):
    """Lowerable form of one sharded NN-Descent iteration for the dry-run.

    The K-NN build is a pure data-parallel workload, so the production
    mesh's two axes are flattened into one 'data' axis (all 256/512 chips
    shard points). Returns (lowered, model_flops) where model_flops is the
    paper's cost model for the iteration's distance evaluations in the
    MXU expansion form (2d flops/pair/direction).
    """
    import numpy as _np
    devs = _np.array(mesh.devices).reshape(-1)
    flat = jax.sharding.Mesh(devs, ("data",))
    P_ = devs.size
    assert n % P_ == 0
    n_local = n // P_
    cfg = DescentConfig(k=k, rho=rho, reorder=False)

    @functools.partial(
        jax.shard_map,
        mesh=flat,
        in_specs=(P(), P("data", None), P("data", None), P("data", None),
                  P("data", None)),
        out_specs=((P("data", None), P("data", None), P("data", None)),
                   P(), P()),
        check_vma=False,
    )
    def iter_fn(key, x_local, d_, i_, n_):
        x_local = x_local.astype(jnp.float32)
        x2_local = jnp.sum(x_local * x_local, axis=1)
        p = jax.lax.axis_index("data")
        kk = jax.random.fold_in(key, p)
        nl_local = NeighborLists(d_, i_, n_ > 0)
        nl2, upd, ev = nn_descent_sharded_iteration(
            kk, x_local, x2_local, nl_local, cfg, axis="data", P_=P_,
            fetch=fetch)
        return (nl2.dist, nl2.idx, nl2.new.astype(jnp.int8)), upd, ev

    sds = jax.ShapeDtypeStruct
    abstract = (
        sds((), jax.random.key(0).dtype),
        sds((n, d), jnp.float32),
        sds((n, k), jnp.float32),
        sds((n, k), jnp.int32),
        sds((n, k), jnp.int8),
    )
    lowered = jax.jit(iter_fn).lower(*abstract)
    rho_k = cfg.rho_k
    pairs_per_node = rho_k * (rho_k - 1) / 2 + rho_k * rho_k
    model_flops = n * pairs_per_node * 2.0 * d
    return lowered, model_flops


def build_knn_graph_sharded(
    mesh: Mesh,
    x: jax.Array,
    k: int = 20,
    *,
    cfg: DescentConfig | None = None,
    key: jax.Array | None = None,
    axis: str = "data",
):
    """Sharded NN-Descent build. Returns (dist, idx-global, iters).

    Every shard_map body below runs jitted: called bare, a shard_map
    body runs primitive by primitive, each a compile of its own; jitted,
    each body compiles once per build."""
    cfg = cfg or DescentConfig(k=k, reorder=False)
    key = jax.random.key(0) if key is None else key
    P_ = mesh.shape[axis]
    n, d = x.shape
    n_local = n // P_

    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(axis, None)),
        out_specs=(P(axis, None), P(axis, None), P()),
        check_vma=False,
    )
    def init_fn(key, x_local):
        p = jax.lax.axis_index(axis)
        kk = jax.random.fold_in(key, p)
        idx = jax.random.randint(kk, (n_local, k), 0, n, dtype=jnp.int32)
        my = p * n_local + jnp.arange(n_local, dtype=jnp.int32)[:, None]
        idx = jnp.where(idx == my, (idx + 1) % n, idx)
        x_local = x_local.astype(jnp.float32)
        feats = _fetch_features_ring(x_local, idx.reshape(-1), axis, P_, n_local)
        feats = feats.reshape(n_local, k, -1)
        dist = jnp.maximum(
            jnp.sum(x_local * x_local, axis=1)[:, None]
            + jnp.sum(feats * feats, axis=-1)
            - 2.0 * jnp.einsum("nd,nkd->nk", x_local, feats,
                               precision=jax.lax.Precision.HIGHEST),
            0.0,
        )
        dist, idx = jax.lax.sort((dist, idx), dimension=1, num_keys=1)
        return dist, idx, jnp.zeros((), jnp.int32)

    dist0, idx0, _ = init_fn(key, x)
    nl = NeighborLists(dist0, idx0, jnp.ones_like(idx0, dtype=bool))

    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P(), P(axis, None), P(axis, None), P(axis, None), P(axis, None),
        ),
        out_specs=(
            (P(axis, None), P(axis, None), P(axis, None)), P(), P(),
        ),
        check_vma=False,
    )
    def iter_fn(key, x_local, d_, i_, n_):
        x_local = x_local.astype(jnp.float32)
        x2_local = jnp.sum(x_local * x_local, axis=1)
        p = jax.lax.axis_index(axis)
        kk = jax.random.fold_in(key, p)
        nl_local = NeighborLists(d_, i_, n_ > 0)
        nl2, upd, ev = nn_descent_sharded_iteration(
            kk, x_local, x2_local, nl_local, cfg, axis=axis, P_=P_,
            fetch=getattr(cfg, "fetch", "a2a"),
        )
        return (nl2.dist, nl2.idx, nl2.new.astype(jnp.int8)), upd, ev

    total_ev = 0
    for it in range(cfg.max_iters):
        key, k_it = jax.random.split(key)
        (d_, i_, nf), upd, ev = iter_fn(
            k_it, x, nl.dist, nl.idx, nl.new.astype(jnp.int8)
        )
        nl = NeighborLists(d_, i_, nf > 0)
        total_ev += int(ev)
        if int(upd) <= cfg.delta * n * k:
            break

    # terminal polish rounds (quality parity with the single-chip build:
    # see DescentConfig.polish / nn_descent.polish_iteration)
    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None), P(axis, None)),
        out_specs=(
            (P(axis, None), P(axis, None), P(axis, None)), P(), P(),
        ),
        check_vma=False,
    )
    def polish_fn(x_local, d_, i_, n_):
        x_local = x_local.astype(jnp.float32)
        x2_local = jnp.sum(x_local * x_local, axis=1)
        nl_local = NeighborLists(d_, i_, n_ > 0)
        nl2, upd, ev = polish_sharded_round(
            x_local, x2_local, nl_local, axis=axis, P_=P_,
            merge_c=min(6 * k, k * k), backend=cfg.backend,
        )
        return (nl2.dist, nl2.idx, nl2.new.astype(jnp.int8)), upd, ev

    polish_updates = []
    for _p in range(cfg.polish):
        (d_, i_, nf), upd_p, ev_p = polish_fn(
            x, nl.dist, nl.idx, nl.new.astype(jnp.int8)
        )
        nl = NeighborLists(d_, i_, nf > 0)
        total_ev += int(ev_p)
        polish_updates.append(int(upd_p))

    # returned distances are exact: difference form on the final lists
    # (the single-chip build's nn_descent.rerank_lists, rows fetched by
    # the request-routed all_to_all)
    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None)),
        out_specs=(P(axis, None), P(axis, None), P(axis, None)),
        check_vma=False,
    )
    def rerank_fn(x_local, i_, n_):
        x_local = x_local.astype(jnp.float32)
        feats, _ = fetch_rows_a2a(
            x_local, i_.reshape(-1), axis=axis, P_=P_, n_local=n_local,
            cap=i_.size,
        )
        dd = metric_mod.exact_sq_l2(
            x_local, feats.reshape(i_.shape + (-1,)), i_)
        return jax.lax.sort((dd, i_, n_), dimension=1, num_keys=1)

    d_, i_, nf = rerank_fn(x, nl.idx, nl.new.astype(jnp.int8))
    nl = NeighborLists(d_, i_, nf > 0)
    return nl.dist, nl.idx, {
        "iters": it + 1,
        "dist_evals": total_ev,
        "polish_updates": tuple(polish_updates),
    }
