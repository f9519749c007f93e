"""Serving search benchmark: the fused batched multi-expansion beam
search vs. the retained greedy ref loop (the tentpole receipt for
kernels/knn_search.py + core/graph_search.py).

Modes (``python benchmarks/bench_search.py --mode ...``):

  * ``compare`` (default) — the acceptance receipt: builds one clustered
    corpus graph (default n=1e5, d=64), then answers the same q=4096
    query batch with the ref loop (``SearchConfig(backend="ref")`` — one
    node expanded per round, per-round argsorts) and the fused batched
    path (blocked distance tile + partial top-C select + sort-free pool
    merge, ``expand`` nodes per round) at the SAME expansion budget.
    Reports QPS for both, recall of both against brute force on a query
    subsample (the gate: fused recall within 0.005 of ref), and the
    paper §3.2 reordering claim measured on the SERVING gather path:
    ``locality_stats`` (in-block fraction / mean gather spread) before
    vs. after ``greedy_reorder``, plus fused QPS on the reordered graph.

  * ``smoke`` — tiny fixed config for the CI benchmark lane (< ~2 min on
    a CPU runner): one build, ref + fused search, emitting
    ``search_recall`` / ``ref_recall`` / ``fused_qps`` / ``ref_qps``,
    gated by benchmarks/check_gate.py (pinned search-recall floor and
    fused QPS >= ref QPS).

  * ``smoke --precision int8|bf16`` — the quant-parity CI step: the same
    smoke corpus answered by the fused fp32 path and the two-stage
    quantized path (quantized candidate scoring + fp32 re-rank, scoring
    on a precomputed QuantizedStore — the serving-cache semantics).
    Emits ``f32_qps`` / ``f32_recall`` / ``quant_qps`` / ``quant_recall``
    into results/bench/search_quant.json (its own sink so it never
    clobbers the gated smoke rows), gated by check_gate.py (pinned
    quantized-recall floor and quant QPS >= f32 QPS).

``compare`` additionally measures the two-stage quantized path (int8 and
bf16) against fused fp32 at the same budget — the receipt for the
mixed-precision datastore. Rows go through benchmarks.common.Sink into
results/bench/search.json (search_quant.json for the quant smoke); the
CI artifact uploads the whole results/bench directory.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import RESULTS_DIR, Sink, timeit
from repro.core import (
    DescentConfig,
    NeighborLists,
    RouterConfig,
    SearchConfig,
    apply_permutation,
    brute_force_knn,
    build_router,
    datasets,
    greedy_reorder,
    heap,
    locality_stats,
    quantize_corpus,
    recall_at_k,
)
from repro.core.graph_search import graph_search
from repro.core.layout import pad_features
from repro.core.nn_descent import build_knn_graph
from repro.core.quantize import mirror_width


def _qps(x, gidx, q, k_out, cfg, key, qstore=None, x2=None, router=None,
         **kw):
    t = timeit(
        lambda: graph_search(x, gidx, q, k_out=k_out, key=key, cfg=cfg,
                             qstore=qstore, x2=x2, router=router),
        **kw,
    )
    return q.shape[0] / t, t


def _interleaved_qps(runs: dict, qn: int, reps: int = 7) -> dict:
    """Median wall time per tagged thunk with the reps INTERLEAVED
    (a-b-a-b...), so slow patches of a shared/noisy runner hit every
    path equally instead of whichever happened to run second. Returns
    {tag: (qps, median_s)}."""
    for fn in runs.values():             # warm every compiled path first
        jax.block_until_ready(fn())
    ts = {tag: [] for tag in runs}
    for _ in range(reps):
        for tag, fn in runs.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts[tag].append(time.perf_counter() - t0)
    return {tag: (qn / float(np.median(v)), float(np.median(v)))
            for tag, v in ts.items()}


def run_compare(n: int = 100_000, d: int = 64, q_n: int = 4096,
                k: int = 16, k_out: int = 10, beam: int = 32,
                rounds: int = 48, expand: int = 6, q_block: int = 512,
                n_eval: int = 1024, sink: Sink | None = None) -> list:
    sink = sink or Sink("search")
    x = datasets.clustered(jax.random.key(0), n, d, 16)
    # graph quality only needs to be good enough for both paths to search;
    # reorder=False so the locality story is measured separately below
    dcfg = DescentConfig(k=k, rho=0.5, max_iters=4, polish=1, reorder=False)
    dist, idx, _ = build_knn_graph(x, k=k, cfg=dcfg, key=jax.random.key(1))
    q = x[:q_n] + 0.01 * jax.random.normal(jax.random.key(2), (q_n, d))

    # ground truth on a subsample (full brute force at 1e5 x 4096 is the
    # point of NOT serving brute force; n_eval rows suffice for recall)
    _, ti = brute_force_knn(x, q[:n_eval], k_out, exclude_self=False)

    key = jax.random.key(3)
    row = {"op": "search_compare", "n": n, "d": d, "q": q_n, "k": k,
           "k_out": k_out, "beam": beam, "rounds": rounds, "expand": expand,
           "q_block": q_block}
    fcfg = SearchConfig(beam=beam, rounds=rounds, expand=expand,
                        q_block=q_block)
    for tag, cfg in (
        ("ref", SearchConfig(beam=beam, rounds=rounds, backend="ref")),
        ("fused", fcfg),
    ):
        qps, t = _qps(x, idx, q, k_out, cfg, key)
        _, gi = graph_search(x, idx, q[:n_eval], k_out=k_out, key=key,
                             cfg=cfg)
        row[f"{tag}_s"] = round(t, 3)
        row[f"{tag}_qps"] = round(qps, 1)
        row[f"{tag}_recall"] = round(float(recall_at_k(gi, ti)), 4)
    row["speedup"] = round(row["fused_qps"] / max(row["ref_qps"], 1e-9), 2)
    row["recall_gap"] = round(row["ref_recall"] - row["fused_recall"], 4)

    # --- routed entry seeding at the SAME budget (the large-n receipt):
    # uniform-random beam entries strand the search far from the query at
    # this scale, so fused recall collapses; the router's hierarchical
    # entries (nearest members of the query's top centroids) start the
    # beam inside the answer's neighborhood and recover it
    # wide member lists (IVF-style: the top-t cells are enumerated nearly
    # in full as seed candidates) because the cheap compare-bench graph is
    # itself the recall ceiling for pure traversal at this n
    router = build_router(
        x, cfg=RouterConfig(n_centroids=512, iters=6, members=256),
        key=jax.random.key(4))
    rcfg = dataclasses.replace(fcfg, router_t=16)
    qps_rt, t_rt = _qps(x, idx, q, k_out, rcfg, key, router=router)
    _, gi_rt = graph_search(x, idx, q[:n_eval], k_out=k_out, key=key,
                            cfg=rcfg, router=router)
    row["routed_s"] = round(t_rt, 3)
    row["routed_qps"] = round(qps_rt, 1)
    row["routed_recall"] = round(float(recall_at_k(gi_rt, ti)), 4)
    row["routed_gain"] = round(row["routed_recall"] - row["fused_recall"], 4)
    sink.row(**row)

    # --- the two-stage quantized path at the SERVING layout and the same
    # budget: production searches go through the store (MutableKNNStore /
    # serve), whose fp32 rows are padded to the 128-lane quantum
    # (layout.pad_features); the quantized mirror keeps only the logical
    # dims (quantize.mirror_width) with per-row scales — a precomputed
    # cache, like the store keeps (never re-quantized per batch). Both
    # paths answer the same padded store + graph; only the candidate-
    # scoring stage differs, and the quantized pool re-ranks fp32. The
    # acceptance claim: int8 QPS above fp32 with recall within 0.02.
    xp = pad_features(x.astype(jnp.float32))
    x2p = jnp.sum(xp * xp, axis=1)
    qp = pad_features(q.astype(jnp.float32))
    qrow = {"op": "search_quant_compare", "n": n, "d": d, "q": q_n,
            "dp_serving": xp.shape[1], "beam": beam, "rounds": rounds,
            "expand": expand}
    stores = {"f32": None}
    cfgs = {"f32": fcfg}
    for prec in ("int8", "bf16"):
        cfgs[prec] = dataclasses.replace(fcfg, precision=prec)
        stores[prec] = quantize_corpus(xp, prec,
                                       width=mirror_width(d, xp.shape[1]))
        jax.block_until_ready(stores[prec].data)
    res = _interleaved_qps(
        {tag: (lambda tag=tag: graph_search(
            xp, idx, qp, k_out=k_out, key=key, cfg=cfgs[tag],
            qstore=stores[tag], x2=x2p))
         for tag in ("f32", "int8", "bf16")},
        q_n,
    )
    for tag in ("f32", "int8", "bf16"):
        _, gi = graph_search(xp, idx, qp[:n_eval], k_out=k_out, key=key,
                             cfg=cfgs[tag], qstore=stores[tag], x2=x2p)
        qrow[f"{tag}_s"] = round(res[tag][1], 3)
        qrow[f"{tag}_qps"] = round(res[tag][0], 1)
        qrow[f"{tag}_recall"] = round(float(recall_at_k(gi, ti)), 4)
    qrow["int8_speedup_vs_f32"] = round(
        qrow["int8_qps"] / max(qrow["f32_qps"], 1e-9), 2)
    qrow["int8_recall_gap"] = round(
        qrow["f32_recall"] - qrow["int8_recall"], 4)
    sink.row(**qrow)

    # --- paper §3.2 on the serving gather path: reorder locality + QPS
    nl = NeighborLists(dist, idx, jnp.zeros_like(idx, dtype=bool))
    pre = locality_stats(nl)
    sigma, sigma_inv = greedy_reorder(nl)
    x_r, nl_r = apply_permutation(x.astype(jnp.float32), nl, sigma,
                                  sigma_inv)
    post = locality_stats(nl_r)
    qps_r, _ = _qps(x_r, nl_r.idx, q, k_out, fcfg, key)
    _, gi_r = graph_search(x_r, nl_r.idx, q[:n_eval], k_out=k_out, key=key,
                           cfg=fcfg)
    # returned ids are positions in the reordered array; map back for recall
    gi_orig = jnp.where(gi_r >= 0, sigma_inv[jnp.clip(gi_r, 0, n - 1)], -1)
    sink.row(op="search_reorder_locality",
             in_block_pre=round(pre["in_block_fraction"], 4),
             in_block_post=round(post["in_block_fraction"], 4),
             spread_pre=round(pre["mean_gather_spread"], 1),
             spread_post=round(post["mean_gather_spread"], 1),
             block=pre["block"],
             fused_qps_reordered=round(qps_r, 1),
             fused_recall_reordered=round(
                 float(recall_at_k(gi_orig, ti)), 4))
    return sink.save()


def run_smoke(n: int = 2048, d: int = 16, q_n: int = 512, k: int = 10,
              k_out: int = 10, beam: int = 48, rounds: int = 24,
              expand: int = 4) -> list:
    """CI lane: small seeded ref-vs-fused search (search.json). beam=48
    over an 8-cluster corpus keeps entry coverage off the critical path
    (the K-NN graph has no inter-cluster edges), so the gated recall
    measures the search itself."""
    sink = Sink("search")
    x = datasets.clustered(jax.random.key(5), n, d, 8)
    dcfg = DescentConfig(k=k, rho=1.0, max_iters=10)
    _, idx, _ = build_knn_graph(x, k=k, cfg=dcfg, key=jax.random.key(6))
    q = x[:q_n] + 0.01 * jax.random.normal(jax.random.key(7), (q_n, d))
    _, ti = brute_force_knn(x, q, k_out, exclude_self=False)

    key = jax.random.key(8)
    out = {}
    for tag, cfg in (
        ("ref", SearchConfig(beam=beam, rounds=rounds, backend="ref")),
        ("fused", SearchConfig(beam=beam, rounds=rounds, expand=expand)),
    ):
        qps, t = _qps(x, idx, q, k_out, cfg, key, warmup=1, iters=3)
        _, gi = graph_search(x, idx, q, k_out=k_out, key=key, cfg=cfg)
        out[tag] = (qps, t, float(recall_at_k(gi, ti)))
    sink.row(op="smoke_search", n=n, q=q_n, k=k, beam=beam, rounds=rounds,
             expand=expand,
             ref_s=round(out["ref"][1], 3),
             fused_s=round(out["fused"][1], 3),
             ref_qps=round(out["ref"][0], 1),
             fused_qps=round(out["fused"][0], 1),
             ref_recall=round(out["ref"][2], 4),
             search_recall=round(out["fused"][2], 4),
             speedup=round(out["fused"][0] / max(out["ref"][0], 1e-9), 2))
    return sink.save()


def run_smoke_quant(precision: str, n: int = 2048, d: int = 16,
                    q_n: int = 512, k: int = 10, k_out: int = 10,
                    beam: int = 48, rounds: int = 24, expand: int = 4,
                    qps_n: int = 65536, qps_d: int = 64, qps_q: int = 1024,
                    qps_k: int = 16) -> list:
    """CI quant-parity lane, two sub-measurements in one row:

    * ``quant_recall`` / ``f32_recall`` — end-to-end two-stage search on
      the SAME quality smoke corpus as run_smoke (n=2048, real NN-Descent
      graph), so the quantized recall floor is directly comparable to
      the gated fp32 ``search_recall`` floor.
    * ``quant_qps`` / ``f32_qps`` — serving throughput at the layout and
      scale where the mixed-precision store matters: an n=65536 store at
      the padded serving layout (layout.pad_features, 128 lanes) with a
      random regular graph (graph construction is not under test and a
      random graph maximizes gather entropy — the bandwidth-bound regime
      the int8 mirror exists for), identical graph/budget for both
      paths, reps interleaved so runner noise hits both paths equally.

    Its own sink (search_quant.json) so the gated smoke rows in
    search.json survive; gated by check_gate.py --quant."""
    sink = Sink("search_quant")

    # --- recall parity on the quality corpus
    x = datasets.clustered(jax.random.key(5), n, d, 8)
    dcfg = DescentConfig(k=k, rho=1.0, max_iters=10)
    _, idx, _ = build_knn_graph(x, k=k, cfg=dcfg, key=jax.random.key(6))
    q = x[:q_n] + 0.01 * jax.random.normal(jax.random.key(7), (q_n, d))
    _, ti = brute_force_knn(x, q, k_out, exclude_self=False)
    key = jax.random.key(8)
    fcfg = SearchConfig(beam=beam, rounds=rounds, expand=expand)
    qcfg = dataclasses.replace(fcfg, precision=precision)
    qstore = quantize_corpus(x.astype(jnp.float32), precision)
    recalls = {}
    for tag, cfg, qst in (("f32", fcfg, None), ("quant", qcfg, qstore)):
        _, gi = graph_search(x, idx, q, k_out=k_out, key=key, cfg=cfg,
                             qstore=qst)
        recalls[tag] = float(recall_at_k(gi, ti))

    # --- serving-layout throughput (see docstring)
    xb = datasets.clustered(jax.random.key(15), qps_n, qps_d, 16)
    xbp = pad_features(xb.astype(jnp.float32))
    x2bp = jnp.sum(xbp * xbp, axis=1)
    gidx = heap.init_random(jax.random.key(16), qps_n, qps_k).idx
    qb = pad_features(
        (xb[:qps_q] + 0.01 * jax.random.normal(jax.random.key(17),
                                               (qps_q, qps_d))
         ).astype(jnp.float32))
    scfg = SearchConfig(beam=32, rounds=48, expand=6, q_block=512)
    sqcfg = dataclasses.replace(scfg, precision=precision)
    bstore = quantize_corpus(xbp, precision,
                             width=mirror_width(qps_d, xbp.shape[1]))
    jax.block_until_ready(bstore.data)
    res = _interleaved_qps(
        {"f32": lambda: graph_search(xbp, gidx, qb, k_out=k_out, key=key,
                                     cfg=scfg, x2=x2bp),
         "quant": lambda: graph_search(xbp, gidx, qb, k_out=k_out, key=key,
                                       cfg=sqcfg, qstore=bstore, x2=x2bp)},
        qps_q,
    )
    sink.row(op="smoke_search_quant", precision=precision, n=n, q=q_n,
             k=k, beam=beam, rounds=rounds, expand=expand,
             qps_n=qps_n, qps_d=qps_d, qps_q=qps_q,
             f32_s=round(res["f32"][1], 3),
             quant_s=round(res["quant"][1], 3),
             f32_qps=round(res["f32"][0], 1),
             quant_qps=round(res["quant"][0], 1),
             f32_recall=round(recalls["f32"], 4),
             quant_recall=round(recalls["quant"], 4),
             quant_speedup=round(res["quant"][0] /
                                 max(res["f32"][0], 1e-9), 2))
    return sink.save()


def _metric_sink(op: str, metric: str | None = None) -> Sink:
    """search_metric.json is shared by the --metric and --filter lanes,
    which CI runs as SEPARATE invocations: preload any rows an earlier
    invocation saved (append semantics), dropping only a stale row of
    this same lane so re-runs replace rather than duplicate."""
    sink = Sink("search_metric")
    path = os.path.join(RESULTS_DIR, "search_metric.json")
    if os.path.exists(path):
        with open(path) as f:
            sink.rows = [
                r for r in json.load(f)
                if not (r.get("op") == op
                        and (metric is None or r.get("metric") == metric))
            ]
    return sink


def run_smoke_metric(metric: str, n: int = 2048, d: int = 16,
                     q_n: int = 512, k_out: int = 10, beam: int = 48,
                     rounds: int = 24, expand: int = 4) -> list:
    """CI metric lane: the smoke corpus served under cosine / mips
    through the full store path (MutableKNNStore — transformed rows,
    transformed-row graph, query transform at the search boundary).
    Recall is measured against the NATIVE-metric brute-force oracle
    (descending cosine / inner product), and ``sim_err_rel`` receipts
    the exactness claim: ``similarity_from_dist`` applied to the
    returned transformed-space distances must reproduce the true native
    similarities of the returned rows (relative to the oracle's score
    scale). MIPS builds a denser graph (k=20 vs the smoke k=10):
    max-IP neighbors concentrate on large-norm hub rows, and the
    sparser graph under-connects them (docs/METRICS.md).

    Own sink (search_metric.json, shared with the filter lane) so the
    gated fp32 smoke rows survive; gated by check_gate.py --metric."""
    from repro.core import metric as metric_mod
    from repro.core.online import MutableKNNStore, OnlineConfig

    sink = _metric_sink("smoke_search_metric", metric)
    k = 20 if metric == "mips" else 10
    x = datasets.clustered(jax.random.key(5), n, d, 8)
    q = x[:q_n] + 0.01 * jax.random.normal(jax.random.key(7), (q_n, d))
    store, _ = MutableKNNStore.build(
        x, k=k, cfg=OnlineConfig(metric=metric),
        descent=DescentConfig(k=k, rho=1.0, max_iters=10),
        key=jax.random.key(6))

    # native-metric oracle
    if metric == "cosine":
        xs = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        qs = q / jnp.linalg.norm(q, axis=1, keepdims=True)
        scores = qs @ xs.T
    else:
        scores = q @ x.T
    ti = jax.lax.top_k(scores, k_out)[1]

    key = jax.random.key(8)
    t = timeit(lambda: store.search(q, k_out=k_out, beam=beam,
                                    rounds=rounds, key=key),
               warmup=1, iters=3)
    dd, ii = store.search(q, k_out=k_out, beam=beam, rounds=rounds,
                          key=key)
    rec = float(recall_at_k(ii, ti))

    # exact-similarity receipt on the returned ids
    sim = metric_mod.similarity_from_dist(
        dd, metric, q2=jnp.sum(q.astype(jnp.float32) ** 2, axis=1)[:, None],
        mips_m=store.mips_m)
    true_sim = jnp.take_along_axis(scores, jnp.clip(ii, 0, n - 1), axis=1)
    valid = ii >= 0
    scale = max(1.0, float(jnp.max(jnp.abs(scores))))
    sim_err_rel = float(jnp.max(jnp.where(
        valid, jnp.abs(sim - true_sim), 0.0))) / scale

    sink.row(op="smoke_search_metric", metric=metric, n=n, q=q_n, k=k,
             beam=beam, rounds=rounds, expand=expand,
             search_s=round(t, 3),
             qps=round(q_n / max(t, 1e-9), 1),
             metric_recall=round(rec, 4),
             sim_err_rel=round(sim_err_rel, 8),
             mips_m=round(float(store.mips_m), 4))
    return sink.save()


def run_smoke_filter(n: int = 2048, d: int = 16, q_n: int = 256,
                     k: int = 10, k_out: int = 10, beam: int = 48,
                     rounds: int = 24, expand: int = 4) -> list:
    """CI filtered-search lane: per-query predicate masks on the smoke
    corpus — the two-tenant split (even / odd rows), which admits half
    the corpus per query (``filter_frac`` = 0.5). ``leaked`` counts
    returned ids that violate their query's predicate, summed over four
    variants (fused per-query, fused shared-mask, int8 per-query, ref
    per-query) — the gate pins it to exactly 0. ``filtered_recall`` is
    measured against the predicate-restricted brute-force oracle, so
    the lane also catches a filter path that silently trades recall.

    Shares search_metric.json with the metric lane; gated by
    check_gate.py --metric."""
    from repro.core import metric as metric_mod

    sink = _metric_sink("smoke_search_filter")
    x = datasets.clustered(jax.random.key(5), n, d, 8)
    dcfg = DescentConfig(k=k, rho=1.0, max_iters=10)
    _, idx, _ = build_knn_graph(x, k=k, cfg=dcfg, key=jax.random.key(6))
    q = x[:q_n] + 0.01 * jax.random.normal(jax.random.key(7), (q_n, d))
    key = jax.random.key(8)

    # two tenants: query i sees only rows with id % 2 == i % 2
    parity = jnp.arange(n) % 2
    filt_pq = parity[None, :] == (jnp.arange(q_n)[:, None] % 2)
    filt_shared = parity == 0                     # one tenant, all queries

    # predicate-restricted oracle (per-query tenancy)
    d2 = jnp.sum((q[:, None, :] - x[None]) ** 2, axis=-1)
    ti = jax.lax.top_k(-jnp.where(filt_pq, d2, jnp.inf), k_out)[1]

    fcfg = SearchConfig(beam=beam, rounds=rounds, expand=expand)
    variants = {
        "fused_pq": (fcfg, filt_pq, None),
        "fused_shared": (fcfg, filt_shared, None),
        "int8_pq": (dataclasses.replace(fcfg, precision="int8"), filt_pq,
                    quantize_corpus(x.astype(jnp.float32), "int8")),
        "ref_pq": (SearchConfig(beam=beam, rounds=rounds, backend="ref"),
                   filt_pq, None),
    }
    leaked = 0
    rec = {}
    par = np.asarray(parity)
    for tag, (cfg, filt, qst) in variants.items():
        _, gi = graph_search(x, idx, q, k_out=k_out, key=key, cfg=cfg,
                             filter_ids=filt, qstore=qst)
        gi = np.asarray(gi)
        for r in range(q_n):
            ids = gi[r][gi[r] >= 0]
            want = (r % 2) if filt is filt_pq else 0
            leaked += int((par[ids] != want).sum())
        if filt is filt_pq:
            rec[tag] = float(recall_at_k(jnp.asarray(gi), ti))

    qps_t = timeit(lambda: graph_search(x, idx, q, k_out=k_out, key=key,
                                        cfg=fcfg, filter_ids=filt_pq),
                   warmup=1, iters=3)
    sink.row(op="smoke_search_filter", n=n, q=q_n, k=k, beam=beam,
             rounds=rounds, expand=expand,
             filter_frac=round(metric_mod.filter_frac(filt_pq), 4),
             leaked=leaked,
             filtered_recall=round(rec["fused_pq"], 4),
             filtered_recall_int8=round(rec["int8_pq"], 4),
             filtered_recall_ref=round(rec["ref_pq"], 4),
             filtered_s=round(qps_t, 3),
             filtered_qps=round(q_n / max(qps_t, 1e-9), 1))
    return sink.save()


# the routed-dispatch half of the router lane: run in a forked
# subprocess with a forced multi-device CPU topology (the bench process
# already initialized jax single-device). Cluster-aligned shards +
# route_p=1 means own-cluster top-1 routing: queries spread uniformly
# (q = x[::8] -> n/(8*P) per shard), so route_cap=48 > 32 expected per
# shard and dropped_queries must be exactly 0 — the gate's watch item.
_ROUTED_STATS_SRC = """
import json
import jax, jax.numpy as jnp
from repro.core import DescentConfig, RouterConfig, SearchConfig
from repro.core.distributed import graph_search_sharded
from repro.core.nn_descent import build_knn_graph
from repro.core.router import build_router

P, n, d = 4, 1024, 16
n_local = n // P
cent = jax.random.normal(jax.random.key(0), (P, d)) * 8.0
noise = jax.random.normal(jax.random.key(1), (P, n_local, d)) * 0.5
x = (cent[:, None, :] + noise).reshape(n, d).astype(jnp.float32)
cfg = DescentConfig(k=10, rho=1.0, max_iters=10, reorder=False)
parts = []
for s in range(P):
    _, gi, _ = build_knn_graph(x[s*n_local:(s+1)*n_local], k=10, cfg=cfg,
                               key=jax.random.key(s))
    parts.append(gi)
gidx = jnp.concatenate(parts)
router = build_router(x, cfg=RouterConfig(n_centroids=16, sample=1024),
                      key=jax.random.key(7))
mesh = jax.make_mesh((P,), ("data",))
q = x[::8] + 0.01
scfg = SearchConfig(beam=16, rounds=24, expand=4)
_, _, st = graph_search_sharded(mesh, x, gidx, q, k_out=10, cfg=scfg,
                                key=jax.random.key(2), router=router,
                                route_p=1, route_cap=48, with_stats=True)
st = {k: (v if isinstance(v, (list, tuple, float)) else int(v))
      for k, v in st.items()}
st["platform"] = jax.devices()[0].platform
print("ROUTED_STATS " + json.dumps(st))
"""


def _routed_dispatch_stats(n_devices: int = 4, timeout: int = 600) -> dict:
    """Routed sharded dispatch on a forced n_devices CPU topology, in a
    fork (jax device topology is fixed at first backend init). Returns
    the with_stats dict: fanout / shards / routed / searched / dropped."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"     # the forced CPU topology, always
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(repo, "src"), env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-c", _ROUTED_STATS_SRC],
                          capture_output=True, text=True, env=env,
                          cwd=repo, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"routed-dispatch stats child failed "
            f"(rc={proc.returncode}):\n{proc.stderr}")
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("ROUTED_STATS ")]
    if not lines:
        raise RuntimeError(
            f"routed-dispatch stats child printed no ROUTED_STATS:"
            f"\n{proc.stdout}")
    return json.loads(lines[-1][len("ROUTED_STATS "):])


def run_smoke_router(n: int = 4096, d: int = 16, n_clusters: int = 32,
                     q_n: int = 512, k: int = 10, k_out: int = 10,
                     beam: int = 16, rounds: int = 24,
                     expand: int = 4) -> list:
    """CI router lane: the unit-scale large-n collapse. 32 clusters with
    a beam of 16 means uniform-random entries cover only ~40% of the
    clusters (the K-NN graph has no inter-cluster edges — uncovered
    clusters are unreachable), while the routed entries seed every query
    inside its own cluster at the SAME budget. Emits ``routed_recall`` /
    ``random_recall`` / ``routed_qps`` / ``random_qps`` into
    results/bench/search_router.json (its own sink so the gated fp32
    smoke rows survive), gated by check_gate.py --router.

    The row also carries the routed-DISPATCH stats from a forked
    multi-device run (``_routed_dispatch_stats``): ``dropped_queries``
    must be 0 — a ``route_cap`` regression on the sharded serving path
    silently degrades recall, so the gate makes it loud."""
    sink = Sink("search_router")
    x = datasets.clustered(jax.random.key(5), n, d, n_clusters)
    dcfg = DescentConfig(k=k, rho=1.0, max_iters=10)
    _, idx, _ = build_knn_graph(x, k=k, cfg=dcfg, key=jax.random.key(6))
    q = x[:q_n] + 0.01 * jax.random.normal(jax.random.key(7), (q_n, d))
    _, ti = brute_force_knn(x, q, k_out, exclude_self=False)
    router = build_router(
        x, cfg=RouterConfig(n_centroids=2 * n_clusters),
        key=jax.random.key(9))

    key = jax.random.key(8)
    cfg = SearchConfig(beam=beam, rounds=rounds, expand=expand)
    out = {}
    for tag, rt in (("random", None), ("routed", router)):
        qps, t = _qps(x, idx, q, k_out, cfg, key, router=rt,
                      warmup=1, iters=3)
        _, gi = graph_search(x, idx, q, k_out=k_out, key=key, cfg=cfg,
                             router=rt)
        out[tag] = (qps, t, float(recall_at_k(gi, ti)))
    st = _routed_dispatch_stats()
    sink.row(op="smoke_search_router", n=n, q=q_n, k=k, beam=beam,
             rounds=rounds, expand=expand,
             n_clusters=n_clusters,
             n_centroids=router.centroids.shape[0],
             route_stats_platform=st["platform"],
             route_fanout=st["fanout"],
             route_shards=st["shards"],
             routed_queries=st["routed_queries"],
             searched_queries=st["searched_queries"],
             dropped_queries=st["dropped_queries"],
             random_s=round(out["random"][1], 3),
             routed_s=round(out["routed"][1], 3),
             random_qps=round(out["random"][0], 1),
             routed_qps=round(out["routed"][0], 1),
             random_recall=round(out["random"][2], 4),
             routed_recall=round(out["routed"][2], 4),
             routed_gain=round(out["routed"][2] - out["random"][2], 4))
    return sink.save()


def main(argv: list | None = None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mode", choices=("compare", "smoke"), default="compare")
    p.add_argument("--n", type=int, default=None,
                   help="override corpus size (compare mode)")
    p.add_argument("--q", type=int, default=None,
                   help="override query count (compare mode)")
    p.add_argument("--expand", type=int, default=None,
                   help="override fused expansion width (compare mode)")
    p.add_argument("--precision", choices=("int8", "bf16"), default=None,
                   help="smoke mode: run the two-stage quantized parity "
                        "lane (search_quant.json) instead of the fp32 "
                        "smoke; compare mode measures both regardless")
    p.add_argument("--router", action="store_true",
                   help="smoke mode: run the routed-vs-random entry lane "
                        "(search_router.json) instead of the fp32 smoke; "
                        "compare mode measures the routed path regardless")
    p.add_argument("--metric", choices=("cosine", "mips"), default=None,
                   help="smoke mode: run the metric lane (store build + "
                        "search under cosine/mips vs the native-metric "
                        "oracle, search_metric.json) instead of the fp32 "
                        "smoke")
    p.add_argument("--filter", action="store_true", dest="filter_lane",
                   help="smoke mode: run the filtered-search lane "
                        "(per-query predicate masks, leakage pinned to "
                        "0, search_metric.json) instead of the fp32 "
                        "smoke")
    args = p.parse_args(argv)
    if args.mode == "smoke":
        if args.router:
            return run_smoke_router()
        if args.metric is not None:
            return run_smoke_metric(args.metric)
        if args.filter_lane:
            return run_smoke_filter()
        if args.precision is not None:
            return run_smoke_quant(args.precision)
        return run_smoke()
    kw = {}
    if args.n is not None:
        kw["n"] = args.n
    if args.q is not None:
        kw["q_n"] = args.q
    if args.expand is not None:
        kw["expand"] = args.expand
    return run_compare(**kw)


if __name__ == "__main__":
    main()
